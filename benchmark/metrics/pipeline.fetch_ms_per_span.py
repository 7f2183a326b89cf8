"""Overlap pipeline (ops/phold_span.py try_span): wall milliseconds of
the `fetch` WallChannel phase, the device-to-host copy of a span's
output columns while the device idles, per committed PHOLD device span
in the window.  None where the program records no `fetch` phase.
Moves sim_s_per_wall_s."""


def read(ctx):
    spans = ctx["dispatch"]["phold"]["spans"]
    ph = ctx["phases_s"]
    if spans <= 0 or "fetch" not in ph:
        return None
    return 1e3 * ph["fetch"] / spans
