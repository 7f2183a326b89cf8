"""Multi-device sharded round step.

The reference scales with OS threads over shared memory (scheduler/
worker, SURVEY.md section 2.1); the multi-chip analog shards *hosts*
across devices on a `jax.sharding.Mesh` axis:

- each device owns a contiguous shard of hosts and the packet batch
  those hosts emitted this round;
- propagation math (latency gather, threefry loss, clamp) runs
  shard-locally — identical to the single-chip kernel;
- packets are exchanged to their destination shard with
  `lax.all_to_all` over the ICI (the device-resident replacement for
  the reference's locked per-host event queues, worker.rs:597-607);
- the conservative barrier's global min-next-event-time is a min
  over the mesh axis (an `all_gather` of the shards' minima: the TPU
  compiler reduces 64-bit values only by sum), replacing
  manager.rs:447-487's thread-reduction.

The exchange uses fixed per-shard-pair capacity (static shapes: XLA
requirement); overflow falls back to host-side delivery, which only
affects performance, never correctness, because the host runtime
re-checks every delivered packet.
"""

from __future__ import annotations

import numpy as np

from shadow_tpu.core.rng import (STREAM_EXAMPLE_BATCH, mix_key,
                                 threefry2x32_jax, threefry2x32_np)
from shadow_tpu.core.simtime import TIME_NEVER

_I64_MAX = (1 << 63) - 1

HOST_AXIS = "hosts"


def build_sharded_round_step(mesh, latency_ns: np.ndarray,
                             thresholds: np.ndarray, k0: int, k1: int,
                             exchange_capacity: int):
    """Returns a jitted SPMD round step over `mesh` (axis 'hosts').

    Per-shard inputs (leading dim = n_shards when called globally):
      src_node, dst_node : int32[S, B]   packet endpoints (graph nodes)
      dst_shard          : int32[S, B]   destination host's shard index
      src_host, pkt_seq  : int64/uint32[S, B]
      t_send             : int64[S, B]
      is_ctl, valid      : bool[S, B]
      host_next_event    : int64[S, H]   per-host local next-event times
      window_end, bootstrap_end : int64 scalars (replicated)

    Returns:
      deliver  : int64[S, B] arrival times (computed on owner shard)
      keep     : bool[S, B]
      overflow : bool[S, B]  kept but exceeded the exchange capacity
      reachable, lossy : bool[S, B]  drop diagnostics for tracing
      recv_idx, recv_time : exchanged packet index/time per source shard
      barrier_min : int64[S] global min next event (min over shards)
      min_latency : int64[S] global min kept latency (dynamic runahead)
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    lat = jnp.asarray(latency_ns, dtype=jnp.int64)
    thr = jnp.asarray(thresholds, dtype=jnp.int64)
    key0 = jnp.uint32(k0)
    key1 = jnp.uint32(k1)
    n_shards = mesh.shape[HOST_AXIS]

    def _pmin_i64(x):
        # The global min over the mesh axis.  The TPU compiler lowers
        # only sum all-reduces of 64-bit values, so the shards' minima
        # are gathered (S scalars) and reduced locally: the same value
        # as lax.pmin.
        return jnp.min(lax.all_gather(x, HOST_AXIS))

    def shard_fn(src_node, dst_node, dst_shard, src_host, pkt_seq, t_send,
                 is_ctl, valid, host_next_event, window_end, bootstrap_end):
        # Leading singleton shard dim inside shard_map; flatten it.
        src_node = src_node[0]
        dst_node = dst_node[0]
        dst_shard = dst_shard[0]
        src_host = src_host[0]
        pkt_seq = pkt_seq[0]
        t_send = t_send[0]
        is_ctl = is_ctl[0]
        valid = valid[0]
        host_next_event = host_next_event[0]

        latency = lat[src_node, dst_node]
        reachable = latency < TIME_NEVER
        bits, _ = threefry2x32_jax(key0, key1, src_host.astype(jnp.uint32),
                                   pkt_seq)
        lossy = (bits.astype(jnp.int64) < thr[src_node, dst_node]) \
            & jnp.logical_not(is_ctl) & (t_send >= bootstrap_end)
        deliver = jnp.maximum(t_send + latency, window_end)
        keep = valid & reachable & jnp.logical_not(lossy)

        # ---- Exchange: route kept packets to their destination shard.
        # Fixed capacity C per destination shard; position within the
        # outgoing block assigned by stable cumulative count so ordering
        # (src_host, seq) is preserved per source shard.
        C = exchange_capacity
        # rank of packet i among kept packets with the same dst_shard
        onehot = (dst_shard[None, :] == jnp.arange(n_shards)[:, None]) & keep
        rank = jnp.cumsum(onehot, axis=1) - 1          # [n_shards, B]
        slot_in_dst = jnp.take_along_axis(
            rank, dst_shard[None, :], axis=0)[0]        # [B]
        fits = keep & (slot_in_dst < C)
        overflow = keep & jnp.logical_not(fits)

        # One-pass scatter, O(B): non-fitting packets write out of bounds
        # and are dropped.
        flat = jnp.where(fits, dst_shard * C + slot_in_dst, n_shards * C)
        pkt_ids = jnp.arange(src_node.shape[0], dtype=jnp.int32)
        send_idx = jnp.full(n_shards * C, -1, dtype=jnp.int32) \
            .at[flat].set(pkt_ids, mode="drop").reshape(n_shards, C)
        send_time = jnp.full(n_shards * C, _I64_MAX, dtype=jnp.int64) \
            .at[flat].set(deliver, mode="drop").reshape(n_shards, C)

        # all_to_all over the mesh axis (tiled: [n_shards, C] stays
        # [n_shards, C], row j of the result = what shard j sent to us).
        recv_idx = lax.all_to_all(send_idx, HOST_AXIS, 0, 0, tiled=True)
        recv_time = lax.all_to_all(send_time, HOST_AXIS, 0, 0, tiled=True)

        # ---- Barrier: global min over local host events, local in-flight
        # deliveries, and everything we received.
        local_min = jnp.minimum(
            jnp.min(host_next_event),
            jnp.min(jnp.where(keep, deliver, _I64_MAX)))
        barrier_min = _pmin_i64(local_min)
        # Dynamic-runahead feedback: smallest latency any *delivered*
        # packet used this round, reduced globally (runahead.rs:61).
        min_latency = _pmin_i64(
            jnp.min(jnp.where(keep, latency, _I64_MAX)))

        return (deliver[None], keep[None], overflow[None], reachable[None],
                lossy[None], recv_idx[None], recv_time[None],
                barrier_min[None], min_latency[None])

    specs = P(HOST_AXIS)
    in_specs = (specs,) * 9 + (P(), P())
    out_specs = (specs,) * 7 + (P(HOST_AXIS), P(HOST_AXIS))
    fn = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    return jax.jit(fn)


def _counter_ints(seed: int, field: int, shape, hi: int) -> np.ndarray:
    """Deterministic integers in [0, hi): counter-based threefry keyed
    by (seed, field, flat index) — same shared-RNG family the
    simulation uses, no sequential draw-order dependence."""
    k0, k1 = mix_key(seed, STREAM_EXAMPLE_BATCH)
    n = int(np.prod(shape))
    b0, _ = threefry2x32_np(np.uint32(k0), np.uint32(k1),
                            np.arange(n, dtype=np.uint32),
                            np.uint32(field))
    return (b0.astype(np.uint64) % np.uint64(hi)).reshape(shape)


def make_example_batch(n_shards: int, hosts_per_shard: int,
                       batch_per_shard: int, num_nodes: int, seed: int = 0):
    """Tiny synthetic per-shard packet batches for dry-runs/tests."""
    S, B, H = n_shards, batch_per_shard, hosts_per_shard
    total_hosts = S * H
    src_host = _counter_ints(seed, 0, (S, B), total_hosts).astype(np.int64)
    dst_host = _counter_ints(seed, 1, (S, B), total_hosts).astype(np.int64)
    return {
        "src_node": (src_host % num_nodes).astype(np.int32),
        "dst_node": (dst_host % num_nodes).astype(np.int32),
        "dst_shard": (dst_host // H).astype(np.int32),
        "src_host": src_host,
        "pkt_seq": _counter_ints(seed, 2, (S, B), 1 << 31).astype(np.uint32),
        "t_send": np.full((S, B), 1_000_000_000, dtype=np.int64),
        "is_ctl": np.zeros((S, B), dtype=bool),
        "valid": np.ones((S, B), dtype=bool),
        "host_next_event": np.full((S, H), 2_000_000_000, dtype=np.int64),
    }
