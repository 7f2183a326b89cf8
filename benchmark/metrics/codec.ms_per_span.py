"""Span codec (ops/phold_span.py export / convert / import): wall
milliseconds of those WallChannel phases per committed PHOLD device
span in the window.  Moves sim_s_per_wall_s."""

PHASES = ("export", "convert", "import")


def read(ctx):
    spans = ctx["dispatch"]["phold"]["spans"]
    ph = ctx["phases_s"]
    if spans <= 0 or not any(p in ph for p in PHASES):
        return None
    return 1e3 * sum(ph.get(p, 0.0) for p in PHASES) / spans
