"""What a run simulated up to a commit boundary, and its comparison
with the configuration's plain reference (`benchmark/reference/
<name>.py`, named by the configuration's `reference` key).  Every
number compared is a count of differences, each with its limit."""

from __future__ import annotations

from harness import registry


def snapshot(manager, sim_ns: int, rounds: int) -> dict:
    """The packet trace of every host up to the boundary `sim_ns` (all
    of it happened before), and the rounds committed."""
    return {"sim_ns": int(sim_ns), "rounds": int(rounds),
            "lines": {h.name: h.trace_lines() for h in manager.hosts}}


def compare(prog: dict, config: dict, traffic: dict, seed: int) -> dict:
    """name -> (value, limit)."""
    ref = registry.reference(config["reference"])
    return ref.compare(prog, config, traffic, seed)


def correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
