"""Checkpoint-archive CLI (docs/CHECKPOINT.md).

    python -m shadow_tpu.tools.ckpt info   SNAPSHOT
    python -m shadow_tpu.tools.ckpt verify SNAPSHOT
    python -m shadow_tpu.tools.ckpt diff   SNAPSHOT_A SNAPSHOT_B
    python -m shadow_tpu.tools.ckpt fork   SNAPSHOT BASE.yaml \
        VARIANT.yaml [VARIANT2.yaml ...] [--out-dir DIR]
    python -m shadow_tpu.tools.ckpt --smoke [--hosts N]

`info` prints the snapshot's round/sim-time/host-count plus the
section table (sizes + checksums); `verify` re-checksums every section
and gates on the layout version; `diff` compares two snapshots section
by section and names the first differing section — drilling into the
engine plane blob to name the first differing HOST frame.  `fork`
clones one post-ramp snapshot into N config-variant resume points
(ckpt/fork.py: variants may differ only in the fork-safe knobs —
swept DCTCP-K, stop_time — with a clear refusal otherwise; the warm-
start seam the sweep runner uses, docs/SWEEP.md).  `--smoke`
(the ./setup ckpt target) runs a 50-host tgen sim, snapshots it
mid-run, resumes, and byte-compares every determinism-gated artifact
of the resumed run against the straight run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import zlib

from shadow_tpu.ckpt import format as ck


def info(path: str) -> int:
    meta = ck.read_meta(path)
    table = ck.section_table(path)
    print(f"{path}:")
    print(f"  layout version : {ck.CK_VERSION}")
    print(f"  round          : {meta['rounds']} "
          f"(span rounds {meta['span_rounds']})")
    print(f"  sim time       : {meta['next_start_ns'] / 1e9:.6f} s "
          f"(busy end {meta['busy_end_ns'] / 1e9:.6f} s)")
    print(f"  hosts          : {meta['n_hosts']} "
          f"({'engine' if meta['engine'] else 'object'} path)")
    print(f"  seed           : {meta['seed']}")
    print(f"  runahead       : {meta['runahead_ns']} ns")
    print(f"  faults applied : {meta['faults_applied']}")
    if meta.get("managed"):
        print(f"  managed        : {meta['managed']} restart "
              f"record(s) — resume restarts these binaries fresh "
              f"under final-state gating")
    print(f"  config digest  : {meta['config_digest'][:16]}…")
    print("  sections:")
    for sid, crc, length in table:
        name = ck.CK_SEC_NAMES.get(sid, f"#{sid}")
        print(f"    {name:<8} {length:>12} B  crc32 {crc:08x}")
    sections = ck.read_archive(path)
    if ck.CK_SEC_PLANE in sections:
        _epoch, frames = ck.parse_plane_frames(
            sections[ck.CK_SEC_PLANE])
        n_hosts = sum(1 for fid in frames if fid != ck.CK_GLOBAL_FRAME)
        print(f"  engine plane   : {n_hosts} host frame(s)")
    return 0


def verify(path: str) -> int:
    table = ck.section_table(path)  # magic + layout-version gate
    bad = 0
    off = ck.CK_HDR_BYTES + ck.CK_SEC_HDR_BYTES * len(table)
    with open(path, "rb") as f:
        f.seek(off)
        for sid, crc, length in table:
            payload = f.read(length)
            name = ck.CK_SEC_NAMES.get(sid, f"#{sid}")
            if len(payload) != length:
                print(f"  {name}: TRUNCATED ({len(payload)}/{length} B)")
                bad += 1
                continue
            actual = zlib.crc32(payload) & 0xFFFFFFFF
            if actual != crc:
                print(f"  {name}: CHECKSUM MISMATCH "
                      f"({actual:08x} != {crc:08x})")
                bad += 1
            else:
                print(f"  {name}: ok ({length} B)")
    # The plane blob carries its own (engine-build) layout version.
    if not bad:
        sections = ck.read_archive(path)
        if ck.CK_SEC_PLANE in sections:
            try:
                ck.parse_plane_frames(sections[ck.CK_SEC_PLANE])
            except ck.CkptError as e:
                print(f"  plane: {e}")
                bad += 1
    print("verify:", "FAIL" if bad else "ok")
    return 1 if bad else 0


def diff(path_a: str, path_b: str) -> int:
    sa = ck.read_archive(path_a)
    sb = ck.read_archive(path_b)
    first = None
    for sid in sorted(set(sa) | set(sb)):
        name = ck.CK_SEC_NAMES.get(sid, f"#{sid}")
        a, b = sa.get(sid), sb.get(sid)
        if a == b:
            print(f"  {name}: identical "
                  f"({len(a) if a is not None else 0} B)")
            continue
        if a is None or b is None:
            print(f"  {name}: only in "
                  f"{path_a if b is None else path_b}")
        elif sid == ck.CK_SEC_PLANE:
            ea, fa = ck.parse_plane_frames(a)
            eb, fb = ck.parse_plane_frames(b)
            hosts = sorted(
                fid for fid in set(fa) | set(fb)
                if fa.get(fid) != fb.get(fid))
            named = ["global" if h == ck.CK_GLOBAL_FRAME else f"host {h}"
                     for h in hosts[:8]]
            extra = f" (+{len(hosts) - 8} more)" if len(hosts) > 8 else ""
            print(f"  {name}: DIFFERS — first differing frame(s): "
                  f"{', '.join(named)}{extra}"
                  + (f"; state epoch {ea} vs {eb}" if ea != eb else ""))
        elif sid == ck.CK_SEC_META:
            ma, mb = json.loads(a.decode()), json.loads(b.decode())
            keys = sorted(k for k in set(ma) | set(mb)
                          if ma.get(k) != mb.get(k))
            print(f"  {name}: DIFFERS — keys: {', '.join(keys)}")
        else:
            n = next((i for i, (x, y) in enumerate(zip(a, b))
                      if x != y), min(len(a), len(b)))
            print(f"  {name}: DIFFERS ({len(a)} vs {len(b)} B, "
                  f"first difference at byte {n})")
        if first is None:
            first = name
    if first is None:
        print("diff: identical")
        return 0
    print(f"diff: first differing section: {first}")
    return 1


def fork(snapshot: str, base_yaml: str, variant_yamls: list[str],
         out_dir: str) -> int:
    """`ckpt fork`: one forked archive per variant config, named
    <variant stem>.stck in `out_dir`."""
    from shadow_tpu.ckpt.fork import fork_archive
    from shadow_tpu.core.config import ConfigOptions

    base = ConfigOptions.from_file(base_yaml)
    os.makedirs(out_dir, exist_ok=True)
    for vy in variant_yamls:
        variant = ConfigOptions.from_file(vy)
        stem = os.path.splitext(os.path.basename(vy))[0]
        out = os.path.join(out_dir, f"{stem}.stck")
        keys = fork_archive(snapshot, base, variant, out)
        print(f"forked {out}: "
              + (", ".join(keys) if keys else "identical config"))
    return 0


def _collect(dirpath: str) -> dict:
    """Determinism-gate artifact collection (tests/test_determinism.py
    collect() semantics: metrics.wall and the wall channel stripped,
    volatile processed-config lines normalized)."""
    import re
    out = {}
    for root, _, files in os.walk(dirpath):
        for fn in files:
            p = os.path.join(root, fn)
            rel = os.path.relpath(p, dirpath)
            with open(p, "rb") as f:
                data = f.read()
            if fn == "sim-stats.json":
                stats = json.loads(data)
                stats.get("metrics", {}).pop("wall", None)
                data = json.dumps(stats, indent=2,
                                  sort_keys=True).encode()
            if fn == "flight-wall.json":
                data = b"<wall>"
            if fn == "processed-config.yaml":
                data = re.sub(rb"data_directory: .*", b"<n>", data)
                data = re.sub(rb"directory: .*", b"<n>", data)
            out[rel] = data
    return out


def smoke(n_hosts: int) -> int:
    """50-host run -> snapshot -> resume -> byte-compare (the
    ./setup ckpt target): every determinism-gated artifact of the
    resumed run must equal the straight run's."""
    import tempfile

    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import resume_simulation, run_simulation
    from shadow_tpu.tools.netgen import tcp_stream_yaml

    with tempfile.TemporaryDirectory() as td:
        text = tcp_stream_yaml(n_hosts, loss=0.005, stop_time="2s",
                               seed=11, scheduler="tpu")

        def cfg(sub, snapdir):
            config = ConfigOptions.from_yaml_text(text)
            config.general.data_directory = os.path.join(td, sub)
            config.experimental.sim_netstat = "on"
            config.experimental.sim_fabricstat = "on"
            from shadow_tpu.core.config import CheckpointConfig
            config.checkpoint = CheckpointConfig(
                at_ns=[1_000_000_000],
                directory=os.path.join(td, snapdir))
            return config

        _m, s = run_simulation(cfg("straight", "snaps"),
                               write_data=True)
        if not s.ok:
            print(f"ckpt smoke: sim failed: {s.plugin_errors[:3]}",
                  file=sys.stderr)
            return 1
        snap = os.path.join(td, "snaps", "ckpt-1000000000.stck")
        if not os.path.exists(snap):
            print("ckpt smoke: no snapshot written", file=sys.stderr)
            return 1
        if info(snap) != 0 or verify(snap) != 0:
            return 1
        _m2, s2 = resume_simulation(cfg("resumed", "snaps2"), snap,
                                    write_data=True)
        if not s2.ok:
            print(f"ckpt smoke: resume failed: {s2.plugin_errors[:3]}",
                  file=sys.stderr)
            return 1
        a = _collect(os.path.join(td, "straight"))
        b = _collect(os.path.join(td, "resumed"))
        bad = [rel for rel in sorted(set(a) | set(b))
               if a.get(rel) != b.get(rel)]
        if bad:
            print(f"ckpt smoke: resumed artifacts diverged: {bad}",
                  file=sys.stderr)
            return 1
        # The resumed snapshot schedule was already consumed: the
        # second run writes none (documented: times <= the resume
        # point are skipped).
    print(f"ckpt smoke: ok ({n_hosts} hosts, snapshot at round "
          f"boundary >= 1s, resume byte-identical across "
          f"{len(a)} artifacts)")
    return 0


def smoke_managed(n_procs: int) -> int:
    """Managed-fleet restart smoke (the ./setup managed target):
    `n_procs` REAL binaries under the shim -> snapshot mid-activity ->
    restart-resume -> final-state gate (docs/CHECKPOINT.md "Managed
    processes").  The resumed run carries no byte-continuation
    contract (the binaries re-run), but two resumes of the same
    archive must agree byte-for-byte — both are asserted here."""
    import shutil
    import tempfile

    from shadow_tpu.core.config import ConfigOptions
    from shadow_tpu.core.manager import resume_simulation, run_simulation

    if shutil.which("cc") is None:
        print("managed smoke: skipped (no C toolchain for the shim)",
              file=sys.stderr)
        return 0
    with tempfile.TemporaryDirectory() as td:
        # Shared fleet generator + binary builder (bench's
        # managed-1k/10k rungs use them too): per-server echo budgets
        # and explicit server IPs stay correct at ANY n_procs.
        from shadow_tpu.core.config import CheckpointConfig
        from shadow_tpu.tools.netgen import (compile_echo_binaries,
                                             managed_fleet_yaml)
        bins = compile_echo_binaries(td)
        text = managed_fleet_yaml(bins["udp_echo_server"],
                                  bins["udp_echo_client"], n_procs,
                                  stop_time="20s", seed=7)

        def cfg(sub):
            config = ConfigOptions.from_yaml_text(text)
            config.general.data_directory = os.path.join(td, sub)
            # Boundary mid-activity: clients start at 2s, pings take
            # ~20 ms RTT each, so 2030 ms lands inside the exchange.
            config.checkpoint = CheckpointConfig(
                at_ns=[2_030_000_000],
                directory=os.path.join(td, "snaps"))
            return config

        m, s = run_simulation(cfg("straight"))
        snap = getattr(m, "ckpt_last_path", None)
        if not s.ok or snap is None:
            print(f"managed smoke: straight run failed "
                  f"(ok={s.ok}, snapshot={snap}, "
                  f"{s.plugin_errors[:3]})", file=sys.stderr)
            return 1
        if info(snap) != 0 or verify(snap) != 0:
            return 1
        m2, s2 = resume_simulation(cfg("resumed"), snap)
        if not s2.ok:
            print(f"managed smoke: restart-resume failed the final-"
                  f"state gate: {s2.plugin_errors[:3]}",
                  file=sys.stderr)
            return 1
        m3, s3 = resume_simulation(cfg("resumed2"), snap)
        if not s3.ok or m2.trace_lines() != m3.trace_lines():
            print("managed smoke: two resumes of the same archive "
                  "diverged", file=sys.stderr)
            return 1
        restarted = sum(
            1 for h in m2.hosts for p in h.processes.values()
            if p.exited and p.exit_code == 0)
    print(f"managed smoke: ok ({n_procs} real binaries, snapshot "
          f"mid-activity, restart-resume passed the final-state gate "
          f"with {restarted} clean exits, resume-vs-resume "
          f"byte-identical)")
    return 0


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] in ("info", "verify", "diff", "fork"):
        sub = argparse.ArgumentParser(
            prog=f"shadow_tpu.tools.ckpt {argv[0]}")
        sub.add_argument("snapshot")
        if argv[0] == "diff":
            sub.add_argument("snapshot_b")
        if argv[0] == "fork":
            sub.add_argument("base_yaml")
            sub.add_argument("variant_yamls", nargs="+")
            sub.add_argument("--out-dir", default=".")
        sargs = sub.parse_args(argv[1:])
        try:
            if argv[0] == "info":
                return info(sargs.snapshot)
            if argv[0] == "verify":
                return verify(sargs.snapshot)
            if argv[0] == "fork":
                return fork(sargs.snapshot, sargs.base_yaml,
                            sargs.variant_yamls, sargs.out_dir)
            return diff(sargs.snapshot, sargs.snapshot_b)
        except ck.CkptError as e:
            print(f"ckpt: {e}", file=sys.stderr)
            return 1
    ap = argparse.ArgumentParser(prog="shadow_tpu.tools.ckpt",
                                 description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run the 50-host snapshot/resume smoke and "
                         "exit nonzero unless artifacts byte-match")
    ap.add_argument("--hosts", type=int, default=50,
                    help="host count for --smoke (default 50)")
    ap.add_argument("--smoke-managed", type=int, metavar="N",
                    help="run the managed-fleet restart smoke with N "
                         "real binaries (the ./setup managed target)")
    args = ap.parse_args(argv)
    if args.smoke_managed:
        return smoke_managed(args.smoke_managed)
    if args.smoke:
        return smoke(args.hosts)
    ap.print_usage(sys.stderr)
    print("ckpt: a subcommand (info/verify/diff) or --smoke is "
          "required", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
