"""Analysis pass 4 — cross-layer effect & ownership audit.

Three fail-closed sub-passes over the surfaces the residency protocol
(PR 3), the svc worker pool (PR 13) and the overlapped span pipeline
(PR 16) grew, none of which passes 1-3 cover:

- **4a, engine effect audit** (`effect-*` rules): every exported
  entry point in native/netplane.cpp's PyMethodDef table must be
  classified in `ENTRY_EFFECTS` as a *mutator* (bumps `state_epoch`
  at brace depth 0 — i.e. on every control path — of its wrapper or
  of one delegated helper) or an *observer* (must not bump at all:
  the channel drains, `plane_export`, shape probes).  Unclassified,
  stale, conditionally-bumping-mutator and bumping-observer entries
  are violations.  The classification consumes the SAME extraction
  (`cpp_extract.extract_epoch_effects`) that feeds pass 3's
  `async-hazard` mutator list, so the two can never drift — and an
  explicit `effect-drift` cross-check holds them equal anyway.

- **4b, thread-ownership lint** (`svc-ownership` / `overlap-window`):
  AST reachability from worker entry points (`pool.submit(fn, ..)`,
  `threading.Thread(target=fn)`, `pool.map(fn, ..)`) — any write to
  shared state (self/closure/global attributes or subscripts, or a
  mutating container call on them) outside a `with ..lock..:` block
  violates the host-affine ownership law (`host.id % workers`:
  workers own disjoint host groups and nothing else).  Separately,
  inside an open speculative-dispatch window (`_span_call(..)` not
  yet forced, committed or published as in-flight) writes through a
  deep `self.x.y` chain mutate state the speculation already read.
  Both escape only via the reason-required
  `# shadow-lint: allow[rule] reason` pragma (docs/LINT.md).

- **4c, knob registry** (`knob-*` rules): every `experimental.*` knob
  in core/config.py must be loadable (a from_dict conversion row),
  documented (a row in docs/config_spec.md's experimental table),
  and classified digest-skipped vs digest-included in `KNOB_DIGEST`;
  the skip half must equal ckpt/restore.py's hand-maintained
  `_DIGEST_SKIP_EXPERIMENTAL` tuple, and wall-only knobs must be
  unreachable from the sim-time channel classes.

Every extractor takes injectable text overrides so the mutation
self-tests (tests/test_effects.py) can perturb one surface in memory
and prove the rule bites.  Absent surfaces (no native source, no
docs) make the corresponding rules inert, matching the other passes'
behavior in stripped-down checkouts.
"""

from __future__ import annotations

import ast
import os
import re

from shadow_tpu.analysis.cpp_extract import extract_epoch_effects
from shadow_tpu.analysis.determinism import _pragma_allows, iter_py_files
from shadow_tpu.analysis.report import Violation

RULES = (
    "effect-unclassified", "effect-stale", "effect-mutator-bump",
    "effect-observer-bump", "effect-drift",
    "svc-ownership", "overlap-window",
    "knob-unregistered", "knob-stale", "knob-unloadable",
    "knob-undocumented", "knob-digest-drift", "knob-wall-in-channel",
)

# ---------------------------------------------------------------------------
# 4a: the engine effect registry
# ---------------------------------------------------------------------------

# Every Python-visible engine entry point, by effect.  A new method
# lands only with a row here (effect-unclassified fails closed), and
# the brace-scoped bump scan verifies the declaration against the
# C++ body — a mutator that forgets its bump, or an observer that
# grows one, is caught before any runtime tier.

MUTATORS = frozenset({
    # plane construction / config that future packets observe
    "add_host", "set_callbacks", "set_routing", "set_nt",
    "set_host_rng", "set_host_fault", "set_host_tcp", "set_dctcp_k",
    "set_pcap", "set_tracing", "set_py_work",
    # simulation advance
    "run_until", "run_hosts", "run_hosts_mt", "run_span",
    "advance_clocks", "fire", "deliver", "finish_round",
    "export_round", "scatter_round", "push_inbox", "take_outgoing",
    # device-span import (overwrites host state wholesale)
    "span_import_phold", "span_import_tcp", "span_import_ml_view",
    # snapshot import (rebuilds host state wholesale)
    "plane_import", "host_import",
    # sequence allocators (consume deterministic id streams)
    "next_event_seq", "next_packet_seq", "rng_next",
    # app lifecycle
    "app_spawn", "app_kill", "app_stop", "app_continue",
    "app_teardown",
    # sockets & packets
    "tcp_socket", "udp_socket", "sock_bind", "sock_close", "sock_set",
    "tcp_listen", "tcp_connect", "tcp_accept", "tcp_sendto",
    "tcp_recv", "tcp_shutdown", "tcp_set_nodelay", "tcp_bufs",
    "udp_sendto", "udp_recvfrom", "udp_connect", "udp_push_reply",
    "drop_packet", "free_packet", "intern_packet",
})

OBSERVERS = frozenset({
    # channel drains & enables: TRACE state, not SIMULATION state
    # (the set_flight/set_netstat comment in netplane.cpp is the law)
    "flight_take", "netstat_take", "fabric_take", "pcap_take",
    "trace_entries", "set_flight", "set_netstat", "set_fabric",
    "set_devcap_probe", "netstat_sample", "fabric_sample",
    # counters / probes / shape reads
    "counters", "ml_counters", "mt_stats", "devcap_counters",
    "fabric_counters",
    "drop_causes", "mark_causes", "netstat_totals", "fct_flows",
    "round_size", "peek_next", "peek_deadline", "packet_fields",
    "tcp_info", "sock_addr", "sock_inq", "sock_status",
    # app observation
    "app_poll", "app_status", "app_threads", "app_syscalls",
    # snapshot export is read-only; the epoch read is the guard itself
    "plane_export", "state_epoch",
    # device-span export is read-only (the engine stays authoritative;
    # an aborted span simply never imports)
    "span_export_phold", "span_export_tcp", "span_export_ml_view",
})

ENTRY_EFFECTS = {name: "mutator" for name in MUTATORS}
ENTRY_EFFECTS.update({name: "observer" for name in OBSERVERS})

_CPP_REL = os.path.join("native", "netplane.cpp")


def _read(repo_root: str, *rel):
    try:
        with open(os.path.join(repo_root, *rel)) as fh:
            return fh.read()
    except OSError:
        return None


def _entry_line(cpp_text: str, name: str) -> int:
    m = re.search(r'\{\s*"' + re.escape(name) + r'"\s*,\s*\(PyCFunction\)',
                  cpp_text)
    return cpp_text.count("\n", 0, m.start()) + 1 if m else 0


def check_engine_effects(repo_root: str, cpp_text=None) -> list:
    """4a.  `cpp_text` overrides native/netplane.cpp for self-tests;
    with neither available the rules are inert (no native source)."""
    from_tree = cpp_text is None
    if from_tree:
        cpp_text = _read(repo_root, "native", "netplane.cpp")
        if cpp_text is None:
            return []
    effects = extract_epoch_effects(cpp_text)
    v: list[Violation] = []
    for name in sorted(effects):
        eff = effects[name]
        line = _entry_line(cpp_text, name)
        declared = ENTRY_EFFECTS.get(name)
        if declared is None:
            v.append(Violation(
                "effect-unclassified", _CPP_REL,
                f"engine entry point `{name}` ({eff['cfunc']}) is not "
                f"classified in analysis/effects.py ENTRY_EFFECTS — "
                f"declare it mutator or observer", line=line))
        elif declared == "mutator" and eff["bump"] != "unconditional":
            how = {"none": "never bumps state_epoch",
                   "conditional": "bumps state_epoch only inside nested "
                                  "braces (some mutating control path "
                                  "returns without bumping)",
                   "missing": "has no findable wrapper body"}[eff["bump"]]
            v.append(Violation(
                "effect-mutator-bump", _CPP_REL,
                f"declared mutator `{name}` ({eff['cfunc']}) {how} — "
                f"device-resident span state would survive the mutation",
                line=line))
        elif declared == "observer" and eff["bump"] != "none":
            via = f" via {eff['via']}" if eff["via"] else ""
            v.append(Violation(
                "effect-observer-bump", _CPP_REL,
                f"declared observer `{name}` ({eff['cfunc']}) bumps "
                f"state_epoch{via} — a read would spuriously invalidate "
                f"device-resident span carries", line=line))
    for name in sorted(set(ENTRY_EFFECTS) - set(effects)):
        v.append(Violation(
            "effect-stale", _CPP_REL,
            f"ENTRY_EFFECTS classifies `{name}` but the method table "
            f"exports no such entry point — delete the stale row"))
    # belt-and-braces drift guard: the pass-3 async-hazard list and
    # this audit's mutator view of the same text must agree exactly
    bumping = {n for n, e in effects.items()
               if e["bump"] in ("unconditional", "conditional")}
    if from_tree:
        from shadow_tpu.analysis.determinism import epoch_mutators
        hazard = epoch_mutators(repo_root)
        if hazard != bumping:
            diff = sorted(hazard.symmetric_difference(bumping))
            v.append(Violation(
                "effect-drift", _CPP_REL,
                f"pass-3 async-hazard mutator list disagrees with the "
                f"pass-4 extraction on: {', '.join(diff)} (the two must "
                f"consume one extraction)"))
    return v


# ---------------------------------------------------------------------------
# 4b: thread-ownership lint
# ---------------------------------------------------------------------------

_MUTATING_METHODS = frozenset({
    "append", "appendleft", "add", "update", "pop", "popleft",
    "popitem", "clear", "extend", "extendleft", "remove", "discard",
    "insert", "setdefault", "put", "put_nowait",
})

# window-closing attribute calls / assignments, same event model as
# pass 3's async-hazard rule (determinism._lint_async_fn)
_FORCE_CALLS = frozenset({"asarray", "block_until_ready"})


def _walk_own(node):
    """Walk a statement without descending into nested function or
    class scopes (those are linted on their own)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if not isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                stack.append(child)


def _attr_chain(node):
    """`self.a.b` -> ["self", "a", "b"]; None for non-Name roots."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


class _OwnershipLinter:
    """Per-module worker-reachability + speculative-window scan."""

    def __init__(self, relpath: str, source: str):
        self.relpath = relpath
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=relpath)
        self.violations: list[Violation] = []
        # every function/method/nested def in the module, by name —
        # reachability is name-based and module-local, which matches
        # how the worker pools are actually fed
        self.defs: dict[str, list] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, []).append(node)

    def flag(self, rule: str, lineno: int, message: str):
        if not _pragma_allows(self.lines, lineno, rule):
            self.violations.append(
                Violation(rule, self.relpath, message, line=lineno))

    # -- worker entry points -----------------------------------------
    def _entry_fns(self):
        """(fn-node, how) for every function handed to a worker."""
        out = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            target = None
            how = None
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("submit", "map") and node.args:
                target = node.args[0]
                how = f".{node.func.attr}()"
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "Thread") or \
                    (isinstance(node.func, ast.Name)
                     and node.func.id == "Thread"):
                for kw in node.keywords:
                    if kw.arg == "target":
                        target = kw.value
                        how = "Thread(target=)"
            if target is None:
                continue
            if isinstance(target, ast.Lambda):
                out.append((target, how))
            else:
                name = None
                if isinstance(target, ast.Name):
                    name = target.id
                elif isinstance(target, ast.Attribute):
                    name = target.attr
                for fn in self.defs.get(name, ()):
                    out.append((fn, how))
        return out

    def _reachable(self, roots):
        """Module-local transitive closure over name-matched calls."""
        seen, work = [], [fn for fn, _ in roots]
        while work:
            fn = work.pop()
            if any(fn is s for s in seen):
                continue
            seen.append(fn)
            if isinstance(fn, ast.Lambda):
                body = [fn.body]
            else:
                body = fn.body
            for stmt in body:
                for n in _walk_own(stmt) if isinstance(stmt, ast.stmt) \
                        else ast.walk(stmt):
                    if not isinstance(n, ast.Call):
                        continue
                    name = None
                    if isinstance(n.func, ast.Name):
                        name = n.func.id
                    elif isinstance(n.func, ast.Attribute) and \
                            isinstance(n.func.value, ast.Name) and \
                            n.func.value.id == "self":
                        name = n.func.attr
                    for cand in self.defs.get(name, ()):
                        work.append(cand)
        return seen

    # -- the ownership scan ------------------------------------------
    @staticmethod
    def _locals_of(fn) -> set:
        if isinstance(fn, ast.Lambda):
            names = {a.arg for a in fn.args.args}
            return names
        names = {a.arg for a in fn.args.args + fn.args.kwonlyargs +
                 fn.args.posonlyargs}
        if fn.args.vararg:
            names.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            names.add(fn.args.kwarg.arg)
        for stmt in fn.body:
            for n in _walk_own(stmt):
                if isinstance(n, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign)):
                    tgts = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for t in tgts:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name) and \
                                    isinstance(leaf.ctx, ast.Store):
                                names.add(leaf.id)
                elif isinstance(n, (ast.For,)):
                    for leaf in ast.walk(n.target):
                        if isinstance(leaf, ast.Name):
                            names.add(leaf.id)
                elif isinstance(n, ast.With):
                    for item in n.items:
                        if isinstance(item.optional_vars, ast.Name):
                            names.add(item.optional_vars.id)
                elif isinstance(n, ast.comprehension):
                    for leaf in ast.walk(n.target):
                        if isinstance(leaf, ast.Name):
                            names.add(leaf.id)
        return names

    @staticmethod
    def _is_lock_ctx(item) -> bool:
        try:
            src = ast.unparse(item.context_expr)
        except Exception:  # pragma: no cover - unparse is total on 3.9+
            return False
        return "lock" in src.lower()

    def lint_workers(self):
        entries = self._entry_fns()
        if not entries:
            return
        for fn in self._reachable(entries):
            locals_ = self._locals_of(fn)
            where = getattr(fn, "name", "<lambda>")
            body = [fn.body] if isinstance(fn, ast.Lambda) else fn.body
            self._scan_stmts(body, locals_, False, where)

    def _scan_stmts(self, stmts, locals_, in_lock, where):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.With):
                locked = in_lock or any(self._is_lock_ctx(i)
                                        for i in st.items)
                self._scan_stmts(st.body, locals_, locked, where)
                continue
            if isinstance(st, (ast.If, ast.While)):
                if not in_lock:
                    self._scan_expr(st.test, locals_, where)
                self._scan_stmts(st.body, locals_, in_lock, where)
                self._scan_stmts(st.orelse, locals_, in_lock, where)
                continue
            if isinstance(st, ast.For):
                if not in_lock:
                    self._scan_expr(st.iter, locals_, where)
                self._scan_stmts(st.body, locals_, in_lock, where)
                self._scan_stmts(st.orelse, locals_, in_lock, where)
                continue
            if isinstance(st, ast.Try):
                for blk in (st.body, st.orelse, st.finalbody):
                    self._scan_stmts(blk, locals_, in_lock, where)
                for h in st.handlers:
                    self._scan_stmts(h.body, locals_, in_lock, where)
                continue
            if not in_lock:
                self._scan_expr(st, locals_, where)

    def _scan_expr(self, node, locals_, where):
        for n in _walk_own(node) if isinstance(node, ast.stmt) \
                else ast.walk(node):
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                tgts = n.targets if isinstance(n, ast.Assign) \
                    else [n.target]
                for t in tgts:
                    self._check_target(t, locals_, where, n.lineno)
            elif isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    n.func.attr in _MUTATING_METHODS:
                chain = _attr_chain(n.func.value)
                if chain and (chain[0] == "self"
                              or chain[0] not in locals_):
                    self.flag(
                        "svc-ownership", n.lineno,
                        f"worker-reachable `{where}` mutates shared "
                        f"`{'.'.join(chain)}.{n.func.attr}(..)` outside "
                        f"a lock — workers own only their host group "
                        f"(host.id % workers)")

    def _check_target(self, t, locals_, where, lineno):
        if isinstance(t, ast.Tuple):
            for el in t.elts:
                self._check_target(el, locals_, where, lineno)
            return
        if isinstance(t, ast.Attribute):
            chain = _attr_chain(t)
            if chain and (chain[0] == "self" or chain[0] not in locals_):
                self.flag(
                    "svc-ownership", lineno,
                    f"worker-reachable `{where}` writes shared "
                    f"`{'.'.join(chain)}` outside a lock — workers own "
                    f"only their host group (host.id % workers)")
        elif isinstance(t, ast.Subscript):
            chain = _attr_chain(t.value)
            if chain and chain[0] != "self" and chain[0] in locals_:
                return
            if chain:
                self.flag(
                    "svc-ownership", lineno,
                    f"worker-reachable `{where}` writes shared "
                    f"`{'.'.join(chain)}[..]` outside a lock — workers "
                    f"own only their host group (host.id % workers)")

    # -- the speculative-window scan ---------------------------------
    def lint_overlap_windows(self):
        for fn in ast.walk(self.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._lint_window_fn(fn)

    def _lint_window_fn(self, fn):
        events = []  # (lineno, col, kind, payload)
        for stmt in fn.body:
            for n in _walk_own(stmt):
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute):
                    attr = n.func.attr
                    if attr == "_span_call":
                        events.append((n.lineno, n.col_offset, "open",
                                       None))
                    elif attr in _FORCE_CALLS or attr == "_commit_spec" \
                            or "inflight" in attr:
                        events.append((n.lineno, n.col_offset, "close",
                                       None))
                elif isinstance(n, (ast.Assign, ast.AugAssign)):
                    tgts = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for t in tgts:
                        chain = _attr_chain(t) \
                            if isinstance(t, ast.Attribute) else None
                        if chain and "inflight" in chain[-1]:
                            events.append((n.lineno, n.col_offset,
                                           "close", None))
                        elif chain and chain[0] == "self" and \
                                len(chain) >= 3:
                            events.append((n.lineno, n.col_offset,
                                           "write", ".".join(chain)))
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _MUTATING_METHODS:
                    chain = _attr_chain(n.func.value)
                    if chain and chain[0] == "self" and len(chain) >= 2:
                        events.append(
                            (n.lineno, n.col_offset, "write",
                             f"{'.'.join(chain)}.{n.func.attr}(..)"))
        events.sort(key=lambda e: (e[0], e[1]))
        open_ = False
        for lineno, _col, kind, payload in events:
            if kind == "open":
                open_ = True
            elif kind == "close":
                open_ = False
            elif kind == "write" and open_:
                self.flag(
                    "overlap-window", lineno,
                    f"`{fn.name}` mutates `{payload}` while a "
                    f"speculative span dispatch is in flight — force "
                    f"the window (np.asarray / block_until_ready) or "
                    f"publish it (_commit_spec / _inflight) first")


def check_thread_ownership(repo_root: str, paths=None) -> list:
    """4b over shadow_tpu/ (or explicit `paths` for self-tests)."""
    violations: list[Violation] = []
    files = paths if paths is not None else iter_py_files(repo_root)
    for path in files:
        rel = os.path.relpath(path, repo_root)
        with open(path) as fh:
            source = fh.read()
        try:
            linter = _OwnershipLinter(rel, source)
        except SyntaxError as exc:
            violations.append(Violation(
                "parse-error", rel, str(exc), line=exc.lineno or 0))
            continue
        linter.lint_workers()
        linter.lint_overlap_windows()
        violations.extend(linter.violations)
    return violations


# ---------------------------------------------------------------------------
# 4c: the knob registry
# ---------------------------------------------------------------------------

# Every `experimental.*` knob, classified for the checkpoint config
# digest (ckpt/restore.py config_digest): "digest" knobs shape
# simulation bytes and stay in the hash; "skip" knobs are wall-side
# routing/observability only and a resume may change them freely.
# The "skip" half is cross-checked against _DIGEST_SKIP_EXPERIMENTAL
# (knob-digest-drift), so neither table can rot alone.
KNOB_DIGEST = {
    "scheduler": "skip",
    "runahead": "digest",
    "use_dynamic_runahead": "digest",
    "interface_qdisc": "digest",
    "socket_send_buffer": "digest",
    "socket_recv_buffer": "digest",
    "socket_send_autotune": "digest",
    "socket_recv_autotune": "digest",
    "strace_logging_mode": "digest",
    "max_unapplied_cpu_latency": "digest",
    "unblocked_syscall_latency": "digest",
    "unblocked_vdso_latency": "digest",
    "host_cpu_threshold": "digest",
    "host_cpu_precision": "digest",
    "host_cpu_event_cost": "digest",
    "native_preemption_enabled": "digest",
    "native_preemption_native_interval": "digest",
    "native_preemption_sim_interval": "digest",
    "native_file_io_bandwidth": "digest",
    "tpu_max_packets_per_round": "skip",
    "tpu_min_device_batch": "skip",
    "tpu_shards": "skip",
    "tpu_exchange_capacity": "skip",
    "native_dataplane": "skip",
    "tpu_device_spans": "skip",
    "tpu_donate_buffers": "skip",
    "span_overlap": "skip",
    "pallas_queue_kernels": "skip",
    "dev_span_k_init": "skip",
    "dev_span_k_floor": "skip",
    "dev_span_k_shrink": "skip",
    "flight_recorder": "digest",
    "sim_netstat": "digest",
    "netstat_interval": "digest",
    "sim_fabricstat": "digest",
    "fabricstat_interval": "digest",
    "chrome_top_n": "skip",
    "syscall_observatory": "digest",
    "kernel_observatory": "digest",
    "syscall_service_plane": "skip",
    "managed_death_poll": "skip",
    "managed_watchdog": "skip",
    "managed_spawn_stagger": "skip",
    "pcap_span_cap": "skip",
    "dctcp_k_pkts": "digest",
    "dctcp_k_bytes": "digest",
    "openssl_crypto_noop": "digest",
    "use_cpu_pinning": "skip",
    "use_perf_timers": "digest",
    "report_errors_to_stderr": "skip",
}

# Knobs that shape WALL behavior only (poll cadences, pinning, stderr
# mirroring): they must be unreachable from the sim-time channel
# classes, whose byte-identity contract admits no wall influence.
WALL_ONLY = frozenset({
    "use_cpu_pinning", "managed_death_poll", "managed_watchdog",
    "managed_spawn_stagger", "report_errors_to_stderr",
})

_CHANNEL_CLASSES = frozenset({
    "SimChannel", "NetstatChannel", "FabricChannel", "KernChannel",
    "FixedRecordChannel", "SyscallChannel", "HostSyscallLog",
})

_CONFIG_REL = os.path.join("shadow_tpu", "core", "config.py")
_RESTORE_REL = os.path.join("shadow_tpu", "ckpt", "restore.py")
_DOCS_REL = os.path.join("docs", "config_spec.md")


def _experimental_yaml_keys(config_text: str) -> dict:
    """{yaml key -> lineno} from to_processed_dict()'s experimental
    dict — the serialization surface, i.e. what actually reaches
    processed-config.yaml and the digest."""
    tree = ast.parse(config_text)
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and \
                fn.name == "to_processed_dict":
            for n in ast.walk(fn):
                if isinstance(n, ast.Dict):
                    keys = {k.value: k.lineno for k in n.keys
                            if isinstance(k, ast.Constant)}
                    if "scheduler" in keys and "runahead" in keys:
                        return keys
    return {}


def _experimental_fields(config_text: str) -> set:
    """Dataclass attribute names of ExperimentalConfig."""
    tree = ast.parse(config_text)
    for cls in ast.walk(tree):
        if isinstance(cls, ast.ClassDef) and \
                cls.name == "ExperimentalConfig":
            return {st.target.id for st in cls.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)}
    return set()


def _loader_map(config_text: str) -> dict:
    """{yaml key -> attr} from from_dict's (yaml, attr, conv) rows —
    a row is what makes a knob loadable AND validated (the conv)."""
    fields = _experimental_fields(config_text)
    tree = ast.parse(config_text)
    out: dict = {}
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and fn.name == "from_dict":
            for n in ast.walk(fn):
                if isinstance(n, ast.Tuple) and len(n.elts) == 3 and \
                        isinstance(n.elts[0], ast.Constant) and \
                        isinstance(n.elts[1], ast.Constant) and \
                        n.elts[1].value in fields:
                    out[n.elts[0].value] = n.elts[1].value
    return out


def _digest_skip_tuple(restore_text: str):
    """(set of yaml keys, lineno) of _DIGEST_SKIP_EXPERIMENTAL."""
    tree = ast.parse(restore_text)
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 and \
                isinstance(n.targets[0], ast.Name) and \
                n.targets[0].id == "_DIGEST_SKIP_EXPERIMENTAL" and \
                isinstance(n.value, (ast.Tuple, ast.List)):
            return ({e.value for e in n.value.elts
                     if isinstance(e, ast.Constant)}, n.lineno)
    return None, 0


def _documented_tokens(docs_text: str):
    """(exact tokens, `_`-suffix tokens, heading lineno) from the
    experimental table's first column.  Combined rows list several
    backticked keys; shorthand like `` `_sim_interval` `` documents
    any key ending in that suffix."""
    exact, suffixes = set(), set()
    lines = docs_text.splitlines()
    start = None
    for i, line in enumerate(lines):
        if re.match(r"##\s+`?experimental`?\s*$", line):
            start = i
            break
    if start is None:
        return exact, suffixes, 0
    for line in lines[start + 1:]:
        if line.startswith("## "):
            break
        if not line.startswith("|"):
            continue
        cells = line.split("|")
        if len(cells) < 2:
            continue
        for tok in re.findall(r"`([\w.]+)`", cells[1]):
            (suffixes if tok.startswith("_") else exact).add(tok)
    return exact, suffixes, start + 1


def _wall_knob_channel_hits(repo_root: str, attr_names: set,
                            channel_paths=None):
    """(relpath, lineno, attr) for wall-only knob attribute reads
    inside sim-time channel class bodies."""
    hits = []
    files = channel_paths if channel_paths is not None \
        else iter_py_files(repo_root)
    for path in files:
        rel = os.path.relpath(path, repo_root)
        with open(path) as fh:
            source = fh.read()
        if not any(a in source for a in attr_names):
            continue
        try:
            tree = ast.parse(source, filename=rel)
        except SyntaxError:
            continue
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            base_names = {b.id for b in cls.bases
                          if isinstance(b, ast.Name)} | \
                         {b.attr for b in cls.bases
                          if isinstance(b, ast.Attribute)}
            if cls.name not in _CHANNEL_CLASSES and \
                    not base_names & _CHANNEL_CLASSES:
                continue
            for n in ast.walk(cls):
                if isinstance(n, ast.Attribute) and \
                        n.attr in attr_names:
                    hits.append((rel, n.lineno, n.attr))
    return hits


def check_knob_registry(repo_root: str, config_text=None,
                        restore_text=None, docs_text=None,
                        channel_paths=None) -> list:
    """4c.  Text overrides inject perturbed surfaces for self-tests."""
    if config_text is None:
        config_text = _read(repo_root, "shadow_tpu", "core", "config.py")
        if config_text is None:
            return []
    if restore_text is None:
        restore_text = _read(repo_root, "shadow_tpu", "ckpt",
                             "restore.py")
    if docs_text is None:
        docs_text = _read(repo_root, "docs", "config_spec.md")

    v: list[Violation] = []
    yaml_keys = _experimental_yaml_keys(config_text)
    loader = _loader_map(config_text)

    for key in sorted(yaml_keys):
        line = yaml_keys[key]
        if key not in KNOB_DIGEST:
            v.append(Violation(
                "knob-unregistered", _CONFIG_REL,
                f"experimental knob `{key}` has no digest "
                f"classification in analysis/effects.py KNOB_DIGEST — "
                f"declare it \"digest\" or \"skip\"", line=line))
        if key not in loader:
            v.append(Violation(
                "knob-unloadable", _CONFIG_REL,
                f"experimental knob `{key}` is serialized by "
                f"to_processed_dict but has no from_dict "
                f"(yaml, attr, conv) row — it cannot be loaded or "
                f"validated", line=line))
    for key in sorted(set(KNOB_DIGEST) - set(yaml_keys)):
        v.append(Violation(
            "knob-stale", _CONFIG_REL,
            f"KNOB_DIGEST classifies `{key}` but to_processed_dict "
            f"serializes no such experimental knob — delete the stale "
            f"row"))

    if docs_text is not None:
        exact, suffixes, heading = _documented_tokens(docs_text)
        for key in sorted(yaml_keys):
            if key in exact or any(key.endswith(s) for s in suffixes):
                continue
            v.append(Violation(
                "knob-undocumented", _DOCS_REL,
                f"experimental knob `{key}` has no row in the "
                f"`## experimental` table", line=heading))

    if restore_text is not None:
        skip_tuple, line = _digest_skip_tuple(restore_text)
        if skip_tuple is not None:
            declared_skip = {k for k, kind in KNOB_DIGEST.items()
                             if kind == "skip"}
            if skip_tuple != declared_skip:
                only_restore = sorted(skip_tuple - declared_skip)
                only_registry = sorted(declared_skip - skip_tuple)
                detail = []
                if only_restore:
                    detail.append("only in _DIGEST_SKIP_EXPERIMENTAL: "
                                  + ", ".join(only_restore))
                if only_registry:
                    detail.append("only in KNOB_DIGEST: "
                                  + ", ".join(only_registry))
                v.append(Violation(
                    "knob-digest-drift", _RESTORE_REL,
                    "_DIGEST_SKIP_EXPERIMENTAL and KNOB_DIGEST's "
                    "\"skip\" set disagree (" + "; ".join(detail) + ")",
                    line=line))

    wall_attrs = {loader.get(k, k) for k in WALL_ONLY} | WALL_ONLY
    for rel, lineno, attr in _wall_knob_channel_hits(
            repo_root, wall_attrs, channel_paths=channel_paths):
        v.append(Violation(
            "knob-wall-in-channel", rel,
            f"wall-only knob `{attr}` read inside a sim-time channel "
            f"class — wall knobs must never reach channel bytes",
            line=lineno))
    return v


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def check(repo_root: str, cpp_text=None, paths=None, config_text=None,
          restore_text=None, docs_text=None, channel_paths=None) -> list:
    """Run all three sub-passes; keyword overrides inject in-memory
    surfaces for the mutation self-tests (tests/test_effects.py)."""
    return (check_engine_effects(repo_root, cpp_text=cpp_text)
            + check_thread_ownership(repo_root, paths=paths)
            + check_knob_registry(repo_root, config_text=config_text,
                                  restore_text=restore_text,
                                  docs_text=docs_text,
                                  channel_paths=channel_paths))
