"""Shared device-placement helpers for the span-runner twins.

Both device-span families (ops/phold_span.py, ops/tcp_span.py) cache
their static SoA columns as committed device arrays and, when a
sharded mesh is attached, commit every span input with host-major
columns sharded on the "hosts" axis.  The placement law is identical
for both runners, so it lives here once; the runners mix it in and
provide `self.mesh` and `self._H`.  `scatter_set` is the kernels'
64-bit indexed write.
"""

from __future__ import annotations

import sys
import time

_donate_warned = False

# Abort reason bits — ONE canonical set for both span families (the
# kernels re-export these as module constants; core/manager imports
# AB_EXCH for exchange-capacity attribution).  Trace/outbox overflows
# are capacity problems the driver fixes by growing the buffer and
# retrying; AB_STRUCT means the state left the modelled domain (fall
# back to the C++ path); AB_EXCH is the sharded cross-shard exchange
# overflowing its per-shard capacity — grown and retried, never
# silently truncated.
AB_TRACE = 1
AB_OUT = 2
AB_STRUCT = 4
AB_EXCH = 8

# AOT-compiled span executables, keyed on the _FN_CACHE entry's
# identity (the caches never evict, so id() is stable): one XLA
# compile per built kernel across every Manager in the process —
# the same warm-run property as the jit call cache.  Each value is
# (jax.stages.Compiled, cost_analysis summary dict).
_AOT_CACHE: dict = {}


def scatter_set(a, idx, v, mode="drop"):
    """`a.at[idx].set(v, mode=mode)` with no 64-bit scatter.

    A TPU holds a 64-bit integer as two u32 words and lowers a 64-bit
    scatter to one scatter over the (u32, u32) pair with a tuple
    combiner: on a v5e that costs 12-16 times a single-operand 32-bit
    scatter of the same indices.  So a 64-bit `a` is written as its low
    and high words, two 32-bit scatters with the same indices and mode,
    then put back together: the same bits for every value.  Narrower
    dtypes go straight to `.at[].set`.  The in-range indices must not
    repeat: with duplicates each word could keep another writer's.

    The words come from a truncating convert and a logical shift: the
    TPU compiles them to fewer passes over the target than masking
    does, which counts for a wide target such as a (H, 2048) ring."""
    import jax.numpy as jnp
    from jax import lax
    dt = a.dtype
    if not (jnp.issubdtype(dt, jnp.integer) and dt.itemsize == 8):
        return a.at[idx].set(v, mode=mode)

    def words(x):
        hi = lax.shift_right_logical(x, jnp.asarray(32, dt))
        return (lax.convert_element_type(x, jnp.uint32),
                lax.convert_element_type(hi, jnp.uint32))
    a_lo, a_hi = words(a)
    v_lo, v_hi = words(jnp.asarray(v, dt))
    lo = a_lo.at[idx].set(v_lo, mode=mode).astype(dt)
    hi = a_hi.at[idx].set(v_hi, mode=mode).astype(dt)
    return lax.shift_left(hi, jnp.asarray(32, dt)) | lo


def donation_cache_safe() -> bool:
    """The compile-cache-safe donation guard (BASELINE.md round 6):
    a donated executable loaded back from the PERSISTENT XLA
    compilation cache corrupts the glibc heap on deserialization-hit
    runs, so `experimental.tpu_donate_buffers: on` donates ONLY when
    no persistent cache is configured — never the corrupting
    combination.  Every entry point keeps a persistent cache
    (utils/compile_cache.py), so there `on` never donates; only a
    process with the cache turned off does.  Checked once per kernel
    build (the cache dir is process-static in practice)."""
    global _donate_warned
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir and jax.config.jax_enable_compilation_cache:
        if not _donate_warned:
            _donate_warned = True
            print("[shadow-tpu] tpu_donate_buffers=on ignored: a "
                  "persistent XLA compilation cache is configured "
                  f"({cache_dir!r}) and donated executables corrupt "
                  "the heap on cache-hit runs (BASELINE.md r6)",
                  file=sys.stderr)
        return False
    return True


class SpanMeshMixin:
    """Device placement for span inputs: `mesh` (optional
    jax.sharding.Mesh with a "hosts" axis) and `_H` (host count)
    come from the concrete runner."""

    # Cross-shard exchange capacity (per destination shard per span
    # round) when a mesh with >1 devices is attached: seeded from
    # experimental.tpu_exchange_capacity by the manager's runner
    # factory, grown transactionally on an AB_EXCH abort (exchange
    # overflow is an attributed capacity abort, never truncation).
    exchange_cap = 1 << 12
    exch_grows = 0
    # Distinct devices holding the last dispatch's state (a sharded
    # mesh must spread it, not stack it on the first device).
    state_devices = 0

    @property
    def n_shards(self) -> int:
        """Mesh width the kernel builds for (1 = unsharded).  The
        placement law requires H % n_shards == 0 — the manager never
        attaches a mesh to an unaligned host axis."""
        if self.mesh is None:
            return 1
        return int(self.mesh.devices.size)

    # experimental.tpu_donate_buffers (set by the manager's runner
    # factory): the jitted span loop donates its carry (argnums 0) so
    # XLA reuses the resident buffers in place — behind the
    # cache-safe guard above.
    donate = False

    # ---- Device-kernel observatory (docs/OBSERVABILITY.md) ----------
    # `kern` is the sim-time KernChannel (or None) the driver records
    # one KS_REC into per committed span; `kern_wall` enables the
    # wall-side dispatch attribution (explicit _FN_CACHE accounting,
    # AOT cost_analysis, export/import byte volume) — both set by the
    # manager's runner factory from experimental.kernel_observatory.
    # The integer counters below are class attributes that become
    # instance attributes on first `+=` (the exchange_cap pattern):
    # they live in metrics.wall.dispatch, never in simulation bytes.
    kern = None
    kern_wall = False
    fn_cache_hits = 0        # _FN_CACHE served an already-built fn
    fn_cache_misses = 0      # a fresh kernel build (trace pending)
    fn_cache_build_ns = 0    # wall of each missed fn's FIRST dispatch
    #                          (where jit pays trace + XLA compile)
    device_wall_ns = 0       # wall of every span dispatch, all fates
    rollback_wall_ns = 0     # wall of dispatches that ABORTED (the
    #                          speculative window rolled back unused)
    rollback_reexport_ns = 0  # wall of re-exports an abort forced
    rolled_back_rounds = 0   # rounds stepped then discarded by aborts
    export_bytes = 0         # codec bytes engine -> host, cumulative
    import_bytes = 0         # codec bytes host -> engine, cumulative
    _aot = None              # fn ids whose cost this runner logged
    kernel_costs = None      # Compiled.cost_analysis() per built fn

    # ---- Overlapped span pipeline (ISSUE 16) ------------------------
    # `overlap` (experimental.span_overlap, set by the manager's
    # runner factory) double-buffers dispatch: after a clean commit
    # the driver dispatches the NEXT speculative window asynchronously
    # (jax async dispatch — unforced device arrays) and records it in
    # `_inflight` together with the window params and the post-import
    # engine state_epoch; the host-side import/codec/service work for
    # the committed window then runs while the device executes.  The
    # next try_span LANDS the record iff the params match exactly and
    # the epoch has not moved — any drift refuses the window (the
    # record is discarded UNIMPORTED, so nothing speculative ever
    # reaches engine bytes: byte identity by construction).
    # `pallas_queues` (experimental.pallas_queue_kernels) routes the
    # token-bucket/CoDel scans through ops/pallas_queues.py.
    overlap = False
    pallas_queues = False
    _inflight = None         # {"out", "t_disp", "params", "epoch",
    #                          "t_flush", "ready_at_flush"} or None
    overlap_windows = 0      # speculative windows dispatched
    overlap_hits = 0         # ...landed and consumed
    overlap_refusals = 0     # ...refused (params/epoch mismatch)
    overlap_stale = 0        # refusals caused by state_epoch drift
    overlap_wait_ns = 0      # HOST idle: the `land-wait` legs (block
    #                          on a landed window, device still running)
    overlap_idle_ns = 0      # DEVICE idle the host causes, a lower
    #                          bound (the `pipeline-bubble` wall phase):
    #                          per landed window, K ready -> K+1's async
    #                          dispatch returned (`fetch` + `dispatch`),
    #                          plus the flush->land gap when K was
    #                          already ready at flush.  Uncounted: a K
    #                          that finishes before its flush idles from
    #                          its finish to the flush; one that finishes
    #                          after its flush idles from its finish to
    #                          its landing (the host cannot see either
    #                          finish without polling)
    overlap_pipe_ns = 0      # dispatch -> fetched wall of landed windows

    def _speculate_record(self, out, t_disp, params):
        """The Future-shaped in-flight record: unforced device arrays
        plus everything the landing check needs.  `epoch` is stamped
        at _commit_spec time (AFTER the committed window's import
        bumped it) — the async-hazard lint rule (analysis pass 3)
        enforces that no engine mutator runs between dispatch and
        that commit point."""
        return {"out": out, "t_disp": t_disp, "params": params,
                "epoch": None, "t_flush": 0, "ready_at_flush": False}

    def _commit_spec(self, spec) -> None:
        """Commit point of an async dispatch: stamp the engine epoch
        (all host-side work for the committed window has run; any
        LATER engine mutation invalidates the record at landing) and
        probe — without blocking — whether the device already
        finished, so the flush->land gap can be attributed as device
        idle (ready_at_flush False leaves it uncounted: a lower bound)."""
        spec["epoch"] = self.engine.state_epoch()
        spec["ready_at_flush"] = spec["out"][0]["abort_code"].is_ready()
        spec["t_flush"] = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
        self._inflight = spec

    def _take_inflight(self, params):
        """Land (or refuse) the in-flight window for this try_span
        call.  Returns the record on a hit, None otherwise; ALWAYS
        clears `_inflight` — a refused window is discarded unimported
        (the committed resident state still serves the normal path,
        so refusal costs one dispatch, never correctness)."""
        spec, self._inflight = self._inflight, None
        if spec is None:
            return None
        if spec["params"] != params:
            self.overlap_refusals += 1
            return None
        if self.engine.state_epoch() != spec["epoch"]:
            self.overlap_refusals += 1
            self.overlap_stale += 1
            return None
        self.overlap_hits += 1
        # A landed window is residency-served: its input was rebuilt
        # from the resident device output at speculate time, and no
        # export ran — the residency counter keeps meaning
        # "dispatches served without an engine export".
        self.resident_hits += 1
        if spec["ready_at_flush"]:
            now = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
            self._book_idle(now - spec["t_flush"])
        return spec

    def _book_idle(self, ns: int) -> None:
        """Device idle the host caused (a lower bound, see
        `overlap_idle_ns`): `overlap_idle_ns` and the `pipeline-bubble`
        wall aggregate (no event, so it labels no gap)."""
        self.overlap_idle_ns += ns
        if self.wall is not None:
            self.wall.add("pipeline-bubble", ns)

    def overlap_summary(self) -> dict:
        """The per-family `overlap` block in metrics.wall.dispatch."""
        pipe = max(self.overlap_pipe_ns, 1)
        return {
            "windows": self.overlap_windows,
            "hits": self.overlap_hits,
            "refusals": self.overlap_refusals,
            "stale_refusals": self.overlap_stale,
            "host_idle_wall_s": round(self.overlap_wait_ns / 1e9, 3),
            "device_idle_wall_s": round(self.overlap_idle_ns / 1e9, 3),
            "pipe_wall_s": round(self.overlap_pipe_ns / 1e9, 3),
            "host_idle_frac": round(self.overlap_wait_ns / pipe, 4),
            "device_idle_frac": round(self.overlap_idle_ns / pipe, 4),
        }

    def _cache_fn(self, cache: dict, key, build):
        """THE _FN_CACHE lookup both runners use: explicit hit/miss
        accounting instead of the old compile-vs-execute guessing
        (`metrics.wall.dispatch.fn_cache`).  The build wall lands in
        fn_cache_build_ns at the missed fn's first dispatch — jit
        defers trace+compile to the call, so the insert itself is
        free."""
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
            self.fn_cache_misses += 1
            self.__dict__.setdefault("_built_fns", set()).add(id(fn))
        else:
            self.fn_cache_hits += 1
        return fn

    def _credit_build(self, fn, dt_ns: int) -> None:
        """Credit a first dispatch's wall to fn_cache.build_wall_s
        ONLY when this runner actually built the fn — a cache-served
        kernel's first (warm) dispatch is not a build."""
        if id(fn) in self.__dict__.get("_built_fns", ()):
            self.fn_cache_build_ns += dt_ns

    def abort_kind_counts(self) -> dict:
        """Lazily-created {kind: count} of abort codes seen by this
        runner (struct / exchange-capacity / capacity) — what `trace
        explain` names when rollback waste dominates."""
        d = self.__dict__.get("_abort_kinds")
        if d is None:
            d = self.__dict__["_abort_kinds"] = {}
        return d

    def _note_abort_kind(self, code: int) -> None:
        """Classify one aborted dispatch as exactly ONE kind —
        priority struct > exchange-capacity > capacity (a code can
        carry several bits; counting per bit would make kind counts
        exceed aborted dispatches and skew `trace explain`'s
        dominant-abort ranking).  The AB_* bits are this module's
        canonical constants, re-exported by both kernels."""
        kinds = self.abort_kind_counts()
        if code & AB_STRUCT:
            kind = "struct"
        elif code & AB_EXCH:
            kind = "exchange-capacity"
        else:
            kind = "capacity"
        kinds[kind] = kinds.get(kind, 0) + 1

    def _span_call(self, fn, *args):
        """Dispatch the built span fn.  Under the observatory's wall
        mode (unsharded only — AOT lowering pins input shardings) the
        first dispatch per built fn goes through the explicit AOT path
        (trace -> lower -> compile), so the build wall splits into its
        trace and XLA-compile legs and `Compiled.cost_analysis()`
        yields real flops/bytes per cached kernel instead of a
        heuristic.  The Compiled is cached GLOBALLY alongside the
        _FN_CACHE entry (keyed on the cached fn's identity, which the
        never-evicting cache pins) so a later Manager's runner reuses
        it exactly like the jit call cache — warm runs stay warm.
        A lowering or compile error propagates: it is the compiler's
        verdict on the kernel, and plain jit would only hit it again."""
        if not self.kern_wall or self.mesh is not None:
            return fn(*args)
        if self._aot is None:
            self._aot = set()   # fn ids whose cost this runner logged
            self.kernel_costs = []
        ent = _AOT_CACHE.get(id(fn))
        if ent is None:
            t0 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
            lowered = fn.lower(*args)
            t1 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
            comp = lowered.compile()
            t2 = time.perf_counter_ns()  # shadow-lint: allow[wall-clock] dispatch attribution (metrics.wall)
            cost = comp.cost_analysis()
            ent = _AOT_CACHE[id(fn)] = (comp, {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(
                    cost.get("bytes accessed", 0.0)),
                "trace_wall_s": round((t1 - t0) / 1e9, 3),
                "compile_wall_s": round((t2 - t1) / 1e9, 3),
            })
        if id(fn) not in self._aot:
            self._aot.add(id(fn))
            self.kernel_costs.append(dict(ent[1]))
        return ent[0](*args)

    def _span_jit(self, jax, run):
        """jit the span loop, donating the carry when allowed."""
        if self.donate and donation_cache_safe():
            return jax.jit(run, donate_argnums=(0,))
        return jax.jit(run)

    def donate_active(self) -> bool:
        """Whether the built span fn donates its carry — the
        capacity-abort retry path must re-materialize the input then
        (a donated buffer cannot be dispatched twice)."""
        return self.donate and donation_cache_safe()

    def _put_static(self, jax, v):
        if self.mesh is None:
            return jax.device_put(v)
        from jax.sharding import NamedSharding, PartitionSpec
        spec = (PartitionSpec("hosts")
                if getattr(v, "ndim", 0) >= 1 and v.shape[0] == self._H
                else PartitionSpec())
        return jax.device_put(v, NamedSharding(self.mesh, spec))

    def _build_exchange(self, jax, jnp):
        """The sharded span kernels' cross-shard exchange law (ISSUE
        11 tentpole), shared by both families.  Kept outbox packets
        route to their destination shard through a fixed-capacity
        staging buffer — the slot law is round_step.py's (stable
        cumulative rank per destination shard, capacity E slots per
        shard pair) — and the staged block is sharding-constrained to
        the hosts axis so the partitioner lowers the hop to the
        cross-shard collective (the `lax.all_to_all` of the per-round
        mesh path, in the GSPMD idiom the span while_loop runs in).
        Overflow never truncates: the caller marks AB_EXCH and the
        driver grows `exchange_cap` and retries transactionally.

        Returns (stage, SE): `stage(keep, dst_shard, cols)` maps
        {name: (values[N], fill)} to ({name: staged[SE]}, over[N]).
        """
        from jax.sharding import NamedSharding, PartitionSpec
        spec = NamedSharding(self.mesh, PartitionSpec("hosts"))
        S = self.n_shards
        E = max(int(self.exchange_cap), 8)
        SE = S * E

        def stage(keep, dst_shard, cols):
            onehot = (dst_shard[None, :]
                      == jnp.arange(S)[:, None]) & keep
            rank = jnp.cumsum(onehot, axis=1) - 1
            slot = jnp.take_along_axis(
                rank, dst_shard[None, :], axis=0)[0]
            fits = keep & (slot < E)
            over = keep & ~fits
            flat = jnp.where(fits, dst_shard * E + slot, SE)
            out = {}
            for name, (v, fill) in cols.items():
                buf = jnp.full(SE, fill, v.dtype).at[flat].set(
                    v, mode="drop")
                out[name] = jax.lax.with_sharding_constraint(
                    buf.reshape(S, E), spec).reshape(SE)
            return out, over
        return stage, SE

    def _mesh_put(self, st):
        """Commit every span input to the device mesh: host-major
        columns shard on the hosts axis, everything else replicates.
        Already-committed arrays (the static cache) pass through."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        shard = NamedSharding(self.mesh, PartitionSpec("hosts"))
        repl = NamedSharding(self.mesh, PartitionSpec())
        H = self._H
        return {k: jax.device_put(
                    v, shard if (getattr(v, "ndim", 0) >= 1
                                 and v.shape[0] == H) else repl)
                for k, v in st.items()}
