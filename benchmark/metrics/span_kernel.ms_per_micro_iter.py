"""Span kernel (ops/phold_span.py): device milliseconds of the PHOLD
span executable in the traced window per micro-iteration the committed
spans ran (dispatch counter `micro_iters`).  Aborted dispatches add
device time and no micro-iterations; router.rollback_share shows them.
Moves sim_s_per_wall_s."""

EXECUTABLE = "jit_run"  # the span loop's jit(run), ops/span_mesh.py


def read(ctx):
    tr = ctx["trace"]
    iters = ctx["dispatch"]["phold"]["micro_iters"]
    if tr is None or iters <= 0 or EXECUTABLE not in tr["modules_s"]:
        return None
    return 1e3 * tr["modules_s"][EXECUTABLE] / iters
