"""Overlap pipeline (ops/span_mesh.py `_book_idle`): wall milliseconds
of the `pipeline-bubble` WallChannel aggregate per committed PHOLD
device span in the window.  The bubble is the device idle the host
causes as the host's clock sees it: from a landed span's ready to the
return of the next span's dispatch (`fetch` + `dispatch`), plus the
flush-to-land gap of a span already finished at its flush.  A lower
bound: a span's finish the host does not wait on is not seen
(device.idle_share reads the idle from the trace).  None where the
program records no `pipeline-bubble` phase.  Moves sim_s_per_wall_s."""


def read(ctx):
    spans = ctx["dispatch"]["phold"]["spans"]
    ph = ctx["phases_s"]
    if spans <= 0 or "pipeline-bubble" not in ph:
        return None
    return 1e3 * ph["pipeline-bubble"] / spans
