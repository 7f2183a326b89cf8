"""Span kernel (ops/phold_span.py): the PHOLD span executable's share
of its roofline.  The work is memory-bound: the per-host span state a
PHOLD round needs, read once and written once per micro-iteration,
over the executable's device time at the chip's HBM bandwidth
(benchmark/peaks.json).  Integer operations against the int8 peak
bound it far lower, so bandwidth is the bound that counts.  Moves
sim_s_per_wall_s."""

EXECUTABLE = "jit_run"  # the span loop's jit(run), ops/span_mesh.py

# Frozen from the SoA layout of ops/phold_span.py at PR 22 (the yardstick
# must not move when the kernel does): per host, the inbox (64 entries
# of 3 int64 + 6 int32 fields), the timer heap (16 entries of 2 int64 +
# 2 int32 + 1 bool), the peer list (uint32 each), 101 per-host scalar
# fields (608 bytes), 15 int64 drop causes and 16 int64 syscall slots.
# The CoDel ring and socket queues are ring-indexed, not scanned, and
# are not counted.
INBOX_CAP, INBOX_ENTRY = 64, 48
TIMER_CAP, TIMER_ENTRY = 16, 25
SCALARS = 608 + 15 * 8 + 16 * 8


def span_bytes_per_micro_iter(n_lps: int, peers: int) -> int:
    per_host = (INBOX_CAP * INBOX_ENTRY + TIMER_CAP * TIMER_ENTRY
                + 4 * peers + SCALARS)
    return 2 * n_lps * per_host


def read(ctx):
    tr = ctx["trace"]
    iters = ctx["dispatch"]["phold"]["micro_iters"]
    if tr is None or ctx["peaks"] is None or iters <= 0 \
            or EXECUTABLE not in tr["modules_s"]:
        return None
    p = ctx["config"]["params"]
    nbytes = span_bytes_per_micro_iter(p["n_lps"], p["peers_per_lp"]) \
        * iters
    return 100.0 * nbytes / (tr["modules_s"][EXECUTABLE]
                             * ctx["peaks"]["hbm_bytes_per_s"])
