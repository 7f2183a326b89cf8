"""Span codec (ops/phold_span.py, family 2): wall milliseconds of the
members' dense views' host legs, the WallChannel phases `view-export`
(span_export_ml_view and its conversion) and `view-import` (the
conversion and span_import_ml_view), per committed device span in the
window.  Both sit inside the codec's own phases.  A program without
them reads nothing.  Moves sim_s_per_wall_s."""

PHASES = ("view-export", "view-import")


def read(ctx):
    spans = ctx["dispatch"]["phold"]["spans"]
    ph = ctx["phases_s"]
    if spans <= 0 or not any(p in ph for p in PHASES):
        return None
    return 1e3 * sum(ph.get(p, 0.0) for p in PHASES) / spans
