"""The main path's kernels compile for a TPU v5e, without the chip.

Each test compiles one kernel at the width chip_smoke.py runs against
a described `v5e:2x2` topology: what the TPU compiler refuses fails
here, at no chip time.  A compile that passes is not a chip run.  The
topology is described inside a module fixture (never at import, in a
`skipif` or in conftest.py): only one process may load the TPU library
at a time, so only the worker that runs this file loads it.
"""

import re

import numpy as np
import pytest

# Widths of chip_smoke.py: the 10k Tor-class tier and PHOLD at 10,000
# LPs.  The propagate kernel's largest bucket is the propagator's
# max_batch (TpuPropagator), which bounds every dispatch.
HOSTS = 10_000
MAX_BUCKET = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described "
                            f"here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _node_matrices(nodes=52):
    """Latency/loss matrices of the smoke's 3-tier graph's size; the
    values do not change the program."""
    lat = np.full((nodes, nodes), 10_000_000, np.int64)
    return lat, np.zeros_like(lat)


def test_propagate_kernel_compiles(one_chip):
    import jax

    from shadow_tpu.ops.propagate import build_propagate_kernel
    lat, thr = _node_matrices()
    kernel = build_propagate_kernel(lat, thr, 1, 2)
    cols = [jax.ShapeDtypeStruct((MAX_BUCKET,), dt, sharding=one_chip)
            for dt in (np.int32, np.int32, np.int64, np.uint32, np.int64,
                       np.bool_, np.bool_)]
    scalars = [jax.ShapeDtypeStruct((), np.int64, sharding=one_chip)] * 2
    kernel.lower(*cols, *scalars).compile()


class _Captured(Exception):
    pass


def _capture_phold_span(monkeypatch, n_hosts):
    """The PHOLD span runner and its first dispatch's arguments, from
    a small forced-device run on the CPU (the dispatch itself is cut
    off before it runs)."""
    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import Manager
    from shadow_tpu.ops import span_mesh
    from shadow_tpu.tools.netgen import phold_yaml
    got = {}

    def grab(self, fn, *args):
        got.update(runner=self, args=args)
        raise _Captured()
    monkeypatch.setattr(span_mesh.SpanMeshMixin, "_span_call", grab)
    cfg = ConfigOptions.from_yaml_text(phold_yaml(
        n_hosts, n_init=1, mean_delay_ns=20_000_000, stop_time="0.3s",
        seed=13, scheduler="tpu", device_spans="force",
        peers_per_host=64, experimental_extra={"native_dataplane": "on"}))
    with pytest.raises(_Captured):
        Manager(cfg).run()
    return got["runner"], got["args"]


def test_phold_span_kernel_compiles(monkeypatch, one_chip):
    """The PHOLD span kernel at 10,000 LPs.  The kernel is captured at
    96 LPs and rebuilt at full width: its only host-count inputs are
    the runner's H and the two H-sized buffer caps, and every argument
    with H rows (the only ones with a leading dim of 96) grows to
    10,000 — the shapes a 10,000-LP export gives."""
    import jax
    small = 96
    runner, args = _capture_phold_span(monkeypatch, small)
    runner._H = HOSTS
    runner.cap_out = max(512, 16 * HOSTS)
    runner.cap_tr = max(1 << 14, 64 * HOSTS)
    fn = runner._build(runner._static_cols["peers"].shape[1])

    def widen(v):
        if not hasattr(v, "dtype"):
            return v
        shape = np.shape(v)
        if shape and shape[0] == small:
            shape = (HOSTS,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, v.dtype, sharding=one_chip)
    st = {k: widen(v) for k, v in args[0].items()}
    compiled = fn.lower(st, *map(widen, args[1:])).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 16e9, "does not fit a v5e's 16 GB"
    # A 64-bit indexed write lowers to one scatter over a (u32, u32)
    # pair, 12-16 times the cost of two 32-bit ones (span_mesh.py
    # scatter_set): the module must hold no tuple-result scatter.
    pairs = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(\S+)\s*=\s*\([^=]*\)\s*scatter\(",
                     line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            pairs.append(f"{m.group(1)} "
                         f"({op.group(1) if op else 'no op_name'})")
    assert not pairs, (f"{len(pairs)} tuple-result scatters: "
                       + "; ".join(pairs[:10]))


def _capture_memberlist_span(monkeypatch, n):
    """The span runner and its first dispatch's arguments for a pool
    of `n` memberlist members, from a forced-device run on the CPU."""
    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import Manager
    from shadow_tpu.ops import span_mesh
    got = {}

    def grab(self, fn, *args):
        got.update(runner=self, args=args)
        raise _Captured()
    monkeypatch.setattr(span_mesh.SpanMeshMixin, "_span_call", grab)
    hosts = {f"m{j:05d}": {"network_node_id": 0, "processes": [{
        "path": "memberlist", "args": ["7946", str(j), "m%05d", str(n),
                                       "2718281828"],
        "start_time": "100ms", "expected_final_state": "running"}]}
        for j in range(n)}
    cfg = ConfigOptions.from_dict({
        "general": {"stop_time": "0.3s", "seed": 13},
        "network": {"graph": {"type": "1_gbit_switch"}},
        "experimental": {"scheduler": "tpu", "native_dataplane": "on",
                         "tpu_device_spans": "force"},
        "hosts": hosts})
    with pytest.raises(_Captured):
        Manager(cfg).run()
    return got["runner"], got["args"]


def test_memberlist_span_kernel_compiles(monkeypatch, one_chip):
    """The memberlist span kernel (family 2) at 10,000 members, each
    with its view of all 10,000: captured at 96 members and rebuilt at
    full width.  Every dimension of 96 (hosts, members) grows to
    10,000, and the size-indexed tables (n + 1 entries, three per n)
    grow with it.  It fits a v5e and holds no tuple-result scatter."""
    import jax
    small = 96
    runner, args = _capture_memberlist_span(monkeypatch, small)
    assert runner.family == 2
    runner._H = HOSTS
    runner._ml_n = HOSTS
    runner.cap_out = max(512, 16 * HOSTS)
    runner.cap_tr = max(1 << 14, 64 * HOSTS)
    fn = runner._build(runner._static_cols["peers"].shape[1])
    grow = {small: HOSTS, small + 1: HOSTS + 1,
            3 * (small + 1): 3 * (HOSTS + 1)}

    def widen(v):
        if not hasattr(v, "dtype"):
            return v
        shape = tuple(grow.get(d, d) for d in np.shape(v))
        return jax.ShapeDtypeStruct(shape, v.dtype, sharding=one_chip)
    st = {k: widen(v) for k, v in args[0].items()}
    assert st["ml_vinc"].shape == (HOSTS, HOSTS)
    compiled = fn.lower(st, *map(widen, args[1:])).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 16e9, "does not fit a v5e's 16 GB"
    pairs = [line for line in compiled.as_text().splitlines()
             if re.match(r"\s*(?:ROOT\s+)?%?(\S+)\s*=\s*\([^=]*\)"
                         r"\s*scatter\(", line)]
    assert not pairs, f"{len(pairs)} tuple-result scatters"


@pytest.mark.parametrize("kernel", ["bucket_step", "codel_head"])
def test_pallas_queue_kernel_refused_for_int64(monkeypatch, one_chip,
                                               kernel):
    """Mosaic refuses the queue kernels' 64-bit lanes (ROADMAP A4), so
    `pallas_queue_kernels: on` fails on a TPU with the compiler's
    reason; it is never interpreted there."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.ops import pallas_queues as plq
    monkeypatch.setattr(plq, "_interpret", lambda jax: False)
    i64 = jax.ShapeDtypeStruct((HOSTS,), jnp.int64, sharding=one_chip)
    b = jax.ShapeDtypeStruct((HOSTS,), jnp.bool_, sharding=one_chip)
    if kernel == "bucket_step":
        fn = plq.make_bucket_step(jax, jnp, HOSTS, 1_000_000, True)
        args = (i64, i64, i64, i64, b, i64, i64)
    else:
        fn = plq.make_codel_head(jax, jnp, HOSTS, 5_000_000, 1500, True)
        args = (b, b, i64, i64, i64, i64)
    with pytest.raises(Exception, match="64-bit|X64"):
        jax.jit(fn).lower(*args).compile()


def test_sharded_round_step_compiles(topo):
    """The 4-chip round step: shard-local propagation, the
    `all_to_all` exchange and the min barrier over the mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from shadow_tpu.parallel.round_step import build_sharded_round_step
    mesh = Mesh(np.array(topo.devices[:4]), ("hosts",))
    lat, thr = _node_matrices()
    step = build_sharded_round_step(mesh, lat, thr, 1, 2, 1 << 12)
    S, B, H = 4, 1 << 14, HOSTS // 4
    rows = NamedSharding(mesh, PartitionSpec("hosts"))
    repl = NamedSharding(mesh, PartitionSpec())
    args = [jax.ShapeDtypeStruct((S, B), dt, sharding=rows)
            for dt in (np.int32, np.int32, np.int32, np.int64, np.uint32,
                       np.int64, np.bool_, np.bool_)]
    args.append(jax.ShapeDtypeStruct((S, H), np.int64, sharding=rows))
    args += [jax.ShapeDtypeStruct((), np.int64, sharding=repl)] * 2
    hlo = step.lower(*args).compile().as_text()
    # The gathered minima of the barrier compile to an all-reduce.
    assert "all-to-all" in hlo and "all-reduce" in hlo

