"""A memberlist LAN gossip pool on Shadow's built-in `1_gbit_switch`
graph: one host per member, each running the program's `memberlist`
app (SWIM with Lifeguard, HashiCorp memberlist's DefaultLANConfig).

Hosts are named `h%05d`; `--seed` draws the host each logical member
runs on, as gen/phold.py places its LPs: member j runs on host
`placement(seed, n)[j]`, which the app's `<placement>` argument
resolves the same way (its address table lists each member's host).
Members whose index the traffic marks as crashed (j % crash_every ==
crash_offset) have crashed before t = 0: their hosts run no process,
so datagrams to them find no socket.  The protocol's random draws are
keyed per logical member by the configuration's fixed `protocol_key`,
so every seed runs the same protocol on another placement, or by
`--seed` when the parameters say `key_from_seed`."""

from __future__ import annotations

PATTERN = "h%05d"
M32 = 0xFFFFFFFF


def n_hosts(config: dict) -> int:
    """How many hosts the generated configuration holds: one per member."""
    return config["params"]["n_members"]


def _threefry0(k0: int, k1: int, c0: int, c1: int) -> int:
    """The first output word of threefry2x32-20 (Random123)."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0, x1 = (c0 + k0) & M32, (c1 + k1) & M32
    for d in range(5):
        for r in ((13, 15, 26, 6) if d % 2 == 0 else (17, 29, 16, 24)):
            x0 = (x0 + x1) & M32
            x1 = (((x1 << r) | (x1 >> (32 - r))) & M32) ^ x0
        x0 = (x0 + ks[(d + 1) % 3]) & M32
        x1 = (x1 + ks[(d + 2) % 3] + d + 1) & M32
    return x0


def placement(seed: int, n: int) -> list[int]:
    """The host index of each logical member under `seed`: 0..n-1
    shuffled Fisher-Yates from the top, position i swapping with
    threefry2x32(seed's low and high words; i, 0) % (i + 1), which is
    how the app's `<placement>` argument places the pool
    (docs/config_spec.md)."""
    hosts = list(range(n))
    for i in range(n - 1, 0, -1):
        j = _threefry0(seed & M32, (seed >> 32) & M32, i, 0) % (i + 1)
        hosts[i], hosts[j] = hosts[j], hosts[i]
    return hosts


def protocol_key(config: dict, seed: int) -> int:
    c = config["params"]
    return seed if c.get("key_from_seed") else c["protocol_key"]


def crashed(traffic: dict, j: int) -> bool:
    t = traffic["params"]
    return j % t["crash_every"] == t["crash_offset"]


def make_yaml(config: dict, traffic: dict, seed: int, scheduler: str,
              experimental: dict) -> str:
    c, t = config["params"], traffic["params"]
    n = c["n_members"]
    key = protocol_key(config, seed)
    member = [0] * n
    for j, h in enumerate(placement(seed, n)):
        member[h] = j
    blocks = []
    for h in range(n):
        j, name = member[h], PATTERN % h
        if crashed(traffic, j):
            blocks.append(f"  {name}:\n    network_node_id: 0\n"
                          f"    processes: []")
            continue
        args = f"{c['port']} {j} {PATTERN} {n} {key} {seed}"
        blocks.append(
            f"  {name}:\n    network_node_id: 0\n    processes:\n"
            f'      - {{ path: memberlist, args: "{args}", '
            f"start_time: {t['start_time']}, "
            f"expected_final_state: running }}")
    exp = [f"  scheduler: {scheduler}"]
    exp += [f"  {k}: {v}" for k, v in experimental.items()]
    return (f"general: {{ stop_time: {traffic['horizon']}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: {c['graph']}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")
