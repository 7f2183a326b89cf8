"""Device (TPU v5e): 1 - (union of the device's op intervals) / traced
window, from the profiler trace.  Moves sim_s_per_wall_s."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["device_planes"] == 0 or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
