"""Router (core/manager.py span router): rounds the PHOLD device span
kernel stepped and then discarded (aborted speculative windows) over
all rounds it stepped in the window.  Moves sim_s_per_wall_s."""


def read(ctx):
    s = ctx["dispatch"]["phold"]
    stepped = s["rounds"] + s["rolled_back_rounds"]
    if stepped <= 0:
        return None
    return 100.0 * s["rolled_back_rounds"] / stepped
