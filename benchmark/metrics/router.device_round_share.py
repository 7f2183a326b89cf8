"""Router (core/manager.py): share of the window's packet-propagating
rounds that the device served (inside device spans or through the
per-round device kernel), from the always-on dispatch counters
`rounds_device` / `rounds_dispatched`.  Moves sim_s_per_wall_s."""


def read(ctx):
    d = ctx["dispatch"]
    if d["rounds_dispatched"] <= 0:
        return None
    return 100.0 * d["rounds_device"] / d["rounds_dispatched"]
