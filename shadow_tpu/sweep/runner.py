"""Campaign runner: execute the expanded run matrix in identity-safe
subprocesses, optionally warm-starting fork groups from a shared
post-ramp checkpoint.

Cold path (the default): one `python -m shadow_tpu.sweep.point`
subprocess per point, each with its own data directory and the spec's
per-point wall limit.  Points run one at a time and the runner itself
never imports JAX, so each point's process may hold the accelerator
(a chip belongs to one process at a time); JAX_PLATFORMS, when set,
passes through to the points unchanged.

Warm path (`warm_start: {at_ms: N}`): points are grouped by their
fork-group key (sweep/spec.expand — everything but the fork-safe
axes).  Each group runs ONE ramp subprocess (the group's first point,
with a checkpoint scheduled at the warm-start instant), the snapshot
is forked per point via ckpt/fork.fork_archive (digest re-stamped for
the point's dctcp_k variant), and each point's subprocess RESUMES its
forked archive.  Warm-started variants share the ramp's bytes by
construction — the dataset records `warm_started` so nobody mistakes
a forked point for a cold run of the same config.

Determinism: subprocess stdout/stderr and wall times go to
`run.json`-adjacent logs, never into the dataset; the dataset reads
only the deterministic channels.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from shadow_tpu.sweep import spec as spec_mod


class PointFailure(RuntimeError):
    """A campaign point exited nonzero / timed out past its retry
    budget AND the campaign's max_failed_points allowance; the
    campaign fails loudly rather than aggregating a hole.  Within the
    allowance, failed points are recorded honestly in the manifest
    (and from there in the `.swds` metadata) instead."""


# Wall backoff between per-point retry attempts (docs/ROBUSTNESS.md
# "Self-healing sweeps"): transient failures — an OOM-killed
# subprocess, a wall-limit near-miss on a loaded box — deserve a
# breather; deterministic failures fail every attempt identically.
RETRY_BACKOFF_S = 2.0


def _run_sub(task: dict, task_path: str, log_path: str,
             time_limit_s: float) -> None:
    with open(task_path, "w") as f:
        json.dump(task, f)
    with open(log_path, "w") as log:
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "shadow_tpu.sweep.point",
                 task_path],
                stdout=log, stderr=subprocess.STDOUT,
                timeout=time_limit_s,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__)))))
        except subprocess.TimeoutExpired:
            raise PointFailure(
                f"{os.path.basename(task_path)}: exceeded the "
                f"per-point time limit ({time_limit_s}s) — see "
                f"{log_path}") from None
    if proc.returncode != 0:
        tail = open(log_path).read()[-800:]
        raise PointFailure(
            f"{os.path.basename(task_path)}: exit "
            f"{proc.returncode}\n{tail}")


def point_task(spec: dict, point: dict, data_dir: str) -> dict:
    """THE task-dict recipe for one campaign point — run_campaign and
    bench's identity re-run both build through here, so the two can
    never drift into comparing differently-configured runs."""
    return {
        "yaml": spec_mod.point_yaml(spec, point),
        "data_dir": data_dir,
        "experimental": spec_mod.point_experimental(spec, point),
        "link_interval_ms": spec_mod.validate_spec(
            spec)["link_interval_ms"],
    }


# Sim-time headroom the warm-start ramp runs past its checkpoint
# instant: the snapshot lands at the first conservative-round boundary
# >= at_ms, so the ramp needs a little room after it — but nothing
# like the full scenario stop_time (the ramp is overhead; variants do
# the real running).
RAMP_HEADROOM_NS = 100_000_000


def _scenario_stop_ns(spec: dict) -> int:
    """The campaign's sim stop time in ns (spec.base or the netgen
    scenario default) — the warm-start gate needs it to refuse a ramp
    at/after the end."""
    from shadow_tpu.utils import units
    defaults = {"incast": "3s", "rpc_burst": "3s", "leaf_spine": "5s"}
    stop = spec["base"].get("stop_time",
                            defaults[spec["scenario"]])
    return units.parse_time_ns(stop)


def _write_manifest(out_dir: str, spec: dict, manifest: dict) -> None:
    """Persisted INCREMENTALLY after every point so a killed campaign
    resumes from exactly what completed (`tools/sweep run --resume`)."""
    failed = sorted(pid for pid, ent in manifest.items()
                    if ent.get("status") == "failed")
    with open(os.path.join(out_dir, "manifest.json"), "w") as f:
        json.dump({"spec": spec, "points": manifest,
                   "failed_points": failed}, f,
                  sort_keys=True, indent=1)


def _attempt_point(task: dict, pdir: str, time_limit_s: float,
                   retries: int, log) -> tuple[bool, str, int]:
    """Run one point with the retry budget: (ok, error, attempts).
    The completion marker (`complete.json`) is written only after a
    clean exit — `--resume` trusts the marker, never a half-written
    data dir.  A stale marker from an EARLIER run is removed first,
    so a point that fails now cannot be mistaken for complete by a
    later resume."""
    import time as _walltime
    try:
        os.remove(os.path.join(pdir, "complete.json"))
    except OSError:
        pass
    err = ""
    for attempt in range(retries + 1):
        if attempt:
            log(f"sweep: retry {attempt}/{retries} "
                f"{os.path.basename(pdir)}")
            _walltime.sleep(RETRY_BACKOFF_S * attempt)  # shadow-lint: allow[wall-clock] per-point retry backoff (wall-side fleet control)
        try:
            _run_sub(task, os.path.join(pdir, "task.json"),
                     os.path.join(pdir, "log.txt"), time_limit_s)
        except PointFailure as e:
            err = str(e)
            continue
        with open(os.path.join(pdir, "complete.json"), "w") as f:
            json.dump({"attempts": attempt + 1}, f)
        return True, "", attempt + 1
    return False, err, retries + 1


def run_campaign(spec: dict, out_dir: str,
                 log=lambda msg: print(msg, file=sys.stderr),
                 resume: bool = False) -> dict:
    """Execute every point of `spec` under `out_dir` (one
    subdirectory per point, `<point_id>/`).  Returns the manifest
    points mapping {point_id: {dir, warm_started, group, status,
    attempts}} in matrix order.

    Self-healing (docs/ROBUSTNESS.md): each point retries up to
    `spec.retries` times with bounded backoff; a point that still
    fails is RECORDED (status "failed" + the error) rather than
    aborting, until more than `spec.max_failed_points` have failed —
    then PointFailure aborts the campaign.  With `resume=True`,
    points whose completion marker exists are skipped, so a killed or
    partially-failed campaign re-runs only the missing work."""
    spec = spec_mod.validate_spec(spec)
    points = spec_mod.expand(spec)
    os.makedirs(out_dir, exist_ok=True)
    if resume:
        # point_ids encode only seed+axes: a changed `base`/`scenario`
        # would silently reuse data generated under the OLD spec.
        # The manifest stores the spec it ran with — refuse a resume
        # under a different one.
        man_path = os.path.join(out_dir, "manifest.json")
        if os.path.exists(man_path):
            with open(man_path) as f:
                stored = json.load(f).get("spec")
            if stored is not None and stored != spec:
                raise PointFailure(
                    f"--resume refused: {out_dir} was run under a "
                    f"DIFFERENT spec (point ids encode only "
                    f"seed+axes, so completed points would be reused "
                    f"under the wrong base config) — use a fresh "
                    f"--out directory")
    warm = spec["warm_start"]
    manifest: dict = {}
    failed = 0
    groups: dict = {}
    for p in points:
        groups.setdefault(p["group"], []).append(p)

    if warm is not None:
        ramp_ns = warm["at_ms"] * 1_000_000
        stop_ns = _scenario_stop_ns(spec)
        if ramp_ns >= stop_ns:
            raise spec_mod.SpecError(
                f"warm_start.at_ms ({warm['at_ms']} ms) is not "
                f"before the scenario stop_time "
                f"({stop_ns // 1_000_000} ms)")

    def record_failure(p, pdir, err, attempts) -> None:
        nonlocal failed
        failed += 1
        manifest[p["point_id"]] = {
            "dir": pdir, "group": p["group"],
            "warm_started": warm is not None,
            "status": "failed", "error": err[-800:],
            # 0 = the point itself never ran (its ramp failed).
            "attempts": attempts,
        }
        _write_manifest(out_dir, spec, manifest)
        if failed > spec["max_failed_points"]:
            raise PointFailure(
                f"{p['point_id']}: {err}\n(campaign aborted: "
                f"{failed} failed points exceeds max_failed_points="
                f"{spec['max_failed_points']})")
        log(f"sweep: point {p['point_id']} FAILED "
            f"({failed}/{spec['max_failed_points']} budget) — "
            f"recorded, campaign continues")

    for gname, gpoints in groups.items():
        snap = None
        ramp_task = None
        pending = []
        for p in gpoints:
            pdir = os.path.join(out_dir, p["point_id"])
            if resume and os.path.exists(
                    os.path.join(pdir, "complete.json")):
                log(f"sweep: point {p['point_id']} already complete "
                    f"(resume) — skipped")
                manifest[p["point_id"]] = {
                    "dir": pdir, "group": p["group"],
                    "warm_started": warm is not None,
                    "status": "ok", "attempts": 0,
                }
                continue
            pending.append(p)
        if not pending:
            continue
        if warm is not None:
            # ONE ramp per fork group: the group's first point's
            # scenario config with the group-base experimental
            # values, checkpointed at the warm-start boundary and
            # STOPPED just past it (the full stop_time is the
            # variants' job; stop_time is fork-safe, so the truncated
            # ramp archive forks to full-length variants).
            ramp_ns = warm["at_ms"] * 1_000_000
            ramp_dir = os.path.join(out_dir, f"ramp.{gname}")
            os.makedirs(ramp_dir, exist_ok=True)
            ramp_task = point_task(spec, gpoints[0], ramp_dir)
            ramp_task["checkpoint"] = {"at_ns": [ramp_ns],
                                       "directory": ramp_dir}
            ramp_task["stop_time_ns"] = min(
                _scenario_stop_ns(spec), ramp_ns + RAMP_HEADROOM_NS)
            snap = os.path.join(ramp_dir, f"ckpt-{ramp_ns}.stck")
            if resume and os.path.exists(snap) and os.path.exists(
                    os.path.join(ramp_dir, "complete.json")):
                # The ramp is the expensive part warm-start exists to
                # amortize: a completed ramp's snapshot is reused.
                log(f"sweep: ramp [{gname}] already complete "
                    f"(resume) — snapshot reused")
                ok, err = True, ""
            else:
                log(f"sweep: ramp [{gname}] -> checkpoint at "
                    f"{warm['at_ms']} ms")
                ok, err, _n = _attempt_point(
                    ramp_task, ramp_dir, spec["time_limit_s"],
                    spec["retries"], log)
                if ok and not os.path.exists(snap):
                    ok, err = False, (
                        f"ramp [{gname}] wrote no snapshot at "
                        f"{warm['at_ms']} ms (boundary never reached "
                        f"before stop_time?)")
            if not ok:
                # A dead ramp takes its whole fork group with it —
                # every pending member fails against the budget
                # (attempts 0: the points themselves never ran).
                for p in pending:
                    pdir = os.path.join(out_dir, p["point_id"])
                    os.makedirs(pdir, exist_ok=True)
                    record_failure(p, pdir, f"ramp failed: {err}", 0)
                continue

        for p in pending:
            pdir = os.path.join(out_dir, p["point_id"])
            os.makedirs(pdir, exist_ok=True)
            task = point_task(spec, p, pdir)
            if snap is not None:
                task["resume_from"] = _fork_for_point(
                    ramp_task, task, snap, pdir)
            log(f"sweep: point {p['point_id']}"
                + (" (warm)" if snap is not None else ""))
            ok, err, attempts = _attempt_point(
                task, pdir, spec["time_limit_s"], spec["retries"],
                log)
            if not ok:
                record_failure(p, pdir, err, attempts)
                continue
            manifest[p["point_id"]] = {
                "dir": pdir, "group": p["group"],
                "warm_started": snap is not None,
                "status": "ok", "attempts": attempts,
            }
            _write_manifest(out_dir, spec, manifest)
    _write_manifest(out_dir, spec, manifest)
    return manifest


def _fork_for_point(ramp_task, task, snap, pdir) -> str:
    """Fork the group snapshot into this point's variant archive (the
    base point resumes its own digest through the same seam, so every
    group member takes the identical code path).  Both configs are
    built through sweep/point.build_config from the TASK dicts the
    subprocesses actually ran — the digest the fork re-stamps is
    byte-for-byte the digest the resuming subprocess checks."""
    from shadow_tpu.ckpt.fork import fork_archive
    from shadow_tpu.sweep.point import build_config

    def cfg(t):
        c = build_config(t["yaml"], t["experimental"],
                         t["link_interval_ms"])
        if t.get("stop_time_ns"):
            c.general.stop_time_ns = int(t["stop_time_ns"])
        return c

    out = os.path.join(pdir, "warm.stck")
    fork_archive(snap, cfg(ramp_task), cfg(task), out)
    return out
