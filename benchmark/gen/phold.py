"""PHOLD over one graph node: copied from shadow_tpu/tools/netgen.py
(`phold_args`, `phold_yaml`), with the sizes read from the
configuration file and the traffic file instead of arguments.

One change from the original: `--seed` places the LPs on the hosts.
Logical LP j holds the LCG seed argument `lcg_seed(j)` (netplane.cpp
APP_PHOLD: lcg = f(arg)) and its ring peers are logical LPs j+1 ..
j+k; the seed draws the host (name, address, device lane) each logical
LP runs on.  So every seed runs the same holds, peer draws and rounds,
the same work, on another placement: runs with different seeds spread
no wider than runs of one seed (they differed by about 2% when the
seed drew the LCG seeds, my chip run, PR 22)."""

from __future__ import annotations

import random


def _indent(text: str, pad: str) -> str:
    return "\n".join(pad + line for line in text.splitlines())


def n_hosts(config: dict) -> int:
    """How many hosts the generated configuration holds: one per LP."""
    return config["params"]["n_lps"]


def lcg_seed(j: int) -> int:
    """Logical LP j's LCG seed argument, below 2**31."""
    return (j * 7_919 + 1) % (1 << 31)


def placement(seed: int, n: int) -> list[int]:
    """The host index of each logical LP under `seed`."""
    hosts = list(range(n))
    random.Random(seed).shuffle(hosts)
    return hosts


def phold_yaml(n_hosts: int, n_init: int, mean_delay_ns: int,
               stop_time: str, seed: int, scheduler: str,
               bandwidth: str, latency: str, start_time: str,
               peers_per_host: int, experimental: dict) -> str:
    names = [f"lp{h:04d}" for h in range(n_hosts)]
    place = placement(seed, n_hosts)
    logical = [0] * n_hosts
    for j, h in enumerate(place):
        logical[h] = j
    k = min(peers_per_host, n_hosts - 1)
    blocks = []
    for h, name in enumerate(names):
        j = logical[h]
        peers = [names[place[(j + 1 + m) % n_hosts]] for m in range(k)]
        args = " ".join(["7000", str(lcg_seed(j)), str(n_init),
                         str(mean_delay_ns)] + peers)
        blocks.append(
            f"  {name}:\n    network_node_id: 0\n    processes:\n"
            f'      - {{ path: phold, args: "{args}", '
            f"start_time: {start_time}, "
            f"expected_final_state: running }}")
    exp = [f"  scheduler: {scheduler}"]
    exp += [f"  {k}: {v}" for k, v in experimental.items()]
    gml = (f'graph [ node [ id 0 host_bandwidth_down "{bandwidth}" '
           f'host_bandwidth_up "{bandwidth}" ] '
           f'edge [ source 0 target 0 latency "{latency}" ] ]')
    return (f"general: {{ stop_time: {stop_time}, seed: {seed} }}\n"
            f"network:\n  graph:\n    type: gml\n    inline: |\n"
            f"{_indent(gml, '      ')}\n"
            f"experimental:\n" + "\n".join(exp) + "\n"
            f"hosts:\n" + "\n".join(blocks) + "\n")


def make_yaml(config: dict, traffic: dict, seed: int, scheduler: str,
              experimental: dict) -> str:
    c, t = config["params"], traffic["params"]
    return phold_yaml(c["n_lps"], n_init=t["n_init"],
                      mean_delay_ns=t["mean_delay_ns"],
                      stop_time=traffic["horizon"], seed=seed,
                      scheduler=scheduler, bandwidth=c["bandwidth"],
                      latency=c["latency"], start_time=t["start_time"],
                      peers_per_host=c["peers_per_lp"],
                      experimental=experimental)
