"""The measured window, bounded by commit boundaries of Manager.run.

A commit is the end of a committed conservative round or of a
committed span (C++ or device).  `Manager.run` reads
`self.containment.has_pending` at the top of every iteration of its
round loop, which is exactly such a boundary, whenever `containment`
is set.  No cell configures the program's failure-containment plane
(no `on_failure` policy), so the slot is free: `CommitWindow` takes
it, reads the loop's `start` (the next window start: every event
before it has committed) and `summary.rounds` from the caller's frame,
and always answers False.  It changes nothing the program does; it
ends the run by raising `WindowClosed` at a boundary.  A commit
observer inside Manager.run is a program change, left to a later PR.
"""

from __future__ import annotations

import sys
import time
from typing import Callable, NamedTuple


class WindowClosed(Exception):
    """Raised at the commit boundary that closes the window."""


class Boundary(NamedTuple):
    sim_ns: int   # every event before this simulated time committed
    rounds: int   # conservative rounds committed so far
    wall: float   # time.perf_counter() at the boundary


class CommitWindow:
    """Opens at the first boundary at which `warm(start_ns)` holds and
    closes at the first boundary `seconds` of wall time later."""

    active = True  # Manager.run clears it when its loop ends normally

    def __init__(self, seconds: float, warm: Callable[[int], bool],
                 on_open: Callable[[], None] | None = None,
                 on_close: Callable[[], None] | None = None):
        self.seconds = seconds
        self.warm = warm
        self.on_open = on_open
        self.on_close = on_close
        self.open: Boundary | None = None
        self.close: Boundary | None = None
        self.commits_in_window = 0
        self.longest_commit_s = 0.0
        self._last_wall = 0.0

    @property
    def has_pending(self) -> bool:
        loc = sys._getframe(1).f_locals
        start, rounds = loc["start"], loc["summary"].rounds
        now = time.perf_counter()
        if self.open is None:
            if self.warm(start):
                if self.on_open is not None:
                    self.on_open()
                now = time.perf_counter()
                self.open = Boundary(start, rounds, now)
                self._last_wall = now
            return False
        self.commits_in_window += 1
        self.longest_commit_s = max(self.longest_commit_s,
                                    now - self._last_wall)
        self._last_wall = now
        if now - self.open.wall >= self.seconds:
            self.close = Boundary(start, rounds, now)
            if self.on_close is not None:
                self.on_close()
            raise WindowClosed
        return False


def install(manager, window: CommitWindow) -> None:
    """Put the window in the manager's containment slot, which must be
    empty (a cell that configured containment would lose it)."""
    if manager.containment is not None:
        raise RuntimeError("the cell configures a failure-containment "
                           "policy; the commit window needs that slot")
    manager.containment = window
