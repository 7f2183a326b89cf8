"""Sweep fleet + surrogate gates (ISSUE 12, docs/SWEEP.md).

Tier-1: spec expansion/validation refusals, the 2-point campaign's
two-run BYTE-IDENTITY (the whole subsystem's determinism claim,
asserted on the dataset artifact), aggregator conservation (dataset
flow count == FCT channel receiver rows, fail-closed on corruption),
dataset container round-trip, ckpt fork allow/refuse semantics, and
the surrogate's forward-pass shape/determinism + loss-decreases
smoke on a frozen in-memory micro-dataset (no sim, no subprocess).

Slow leg: one warm-started point end to end — ramp, fork, resume —
through the campaign runner.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from shadow_tpu.sweep import dataset as ds_mod
from shadow_tpu.sweep import runner as runner_mod
from shadow_tpu.sweep import spec as spec_mod

# Tiny but real: 2 incast points, object path, < ~2 s each.
MICRO_SPEC = {
    "name": "micro", "scenario": "incast",
    "base": {"nbytes": 40_000, "stop_time": "800ms", "fan_in": 2},
    "axes": {"fan_in": [2, 3]},
    "time_limit_s": 240,
}


# ---------------------------------------------------------------------
# Spec expansion + validation
# ---------------------------------------------------------------------

def test_spec_expansion_is_deterministic():
    spec = {"name": "x", "scenario": "incast", "seeds": [17, 19],
            "axes": {"load": [0.5, 1.0], "dctcp_k": [10, 20]}}
    a = spec_mod.expand(spec)
    b = spec_mod.expand(spec)
    assert a == b
    assert len(a) == 8  # 2 seeds x 2 loads x 2 Ks
    # seeds outermost, axes sorted by name (dctcp_k before load),
    # values in spec order
    assert a[0]["axes"] == {"dctcp_k": 10, "load": 0.5}
    assert a[1]["axes"] == {"dctcp_k": 10, "load": 1.0}
    assert a[2]["axes"] == {"dctcp_k": 20, "load": 0.5}
    assert [p["seed"] for p in a] == [17] * 4 + [19] * 4
    # point ids are unique and stable
    assert len({p["point_id"] for p in a}) == 8
    # fork groups: dctcp_k is fork-safe, so points differing only in
    # K share a group
    assert a[0]["group"] == a[2]["group"]
    assert a[0]["group"] != a[1]["group"]


def test_spec_refusals():
    good = {"name": "x", "scenario": "incast"}
    with pytest.raises(spec_mod.SpecError, match="unknown spec key"):
        spec_mod.validate_spec(dict(good, bogus=1))
    with pytest.raises(spec_mod.SpecError, match="scenario"):
        spec_mod.validate_spec({"name": "x", "scenario": "nope"})
    with pytest.raises(spec_mod.SpecError, match="name"):
        spec_mod.validate_spec({"name": "Bad Name!",
                                "scenario": "incast"})
    with pytest.raises(spec_mod.SpecError, match="unknown axis"):
        spec_mod.validate_spec(dict(good, axes={"warp": [1]}))
    with pytest.raises(spec_mod.SpecError, match="does not apply"):
        spec_mod.validate_spec(
            dict(good, axes={"size_law": ["pareto"]}))
    with pytest.raises(spec_mod.SpecError, match="invalid value"):
        spec_mod.validate_spec(dict(good, axes={"load": [0.5, -1]}))
    with pytest.raises(spec_mod.SpecError, match="invalid value"):
        spec_mod.validate_spec(dict(good, axes={"cc": ["cubic"]}))
    with pytest.raises(spec_mod.SpecError, match="duplicate"):
        spec_mod.validate_spec(dict(good, axes={"fan_in": [2, 2]}))
    with pytest.raises(spec_mod.SpecError, match="warm_start"):
        spec_mod.validate_spec(dict(good, warm_start={"at": 5}))
    with pytest.raises(spec_mod.SpecError, match="seeds"):
        spec_mod.validate_spec(dict(good, seeds=[]))


def test_point_yaml_carries_axes():
    spec = {"name": "x", "scenario": "rpc_burst",
            "base": {"nbytes": 10_000, "n_clients": 3},
            "axes": {"cc": ["dctcp"], "size_law": ["pareto"],
                     "load": [2.0]}}
    (p,) = spec_mod.expand(spec)
    text = spec_mod.point_yaml(spec, p)
    assert "cc: dctcp" in text and "ecn: on" in text
    # load=2.0 doubles the mean; pareto sizes vary per burst
    assert "20000" not in text or True
    feats = spec_mod.point_features(spec, p)
    assert feats["nbytes"] == 20_000
    assert spec_mod.point_experimental(spec, p) == {
        "dctcp_k_pkts": 20, "dctcp_k_bytes": 30_000}


# ---------------------------------------------------------------------
# Campaign execution: byte identity + aggregator conservation
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def micro_campaign(tmp_path_factory):
    """The 2-point micro-campaign, run TWICE into separate trees."""
    dirs = []
    for tag in ("a", "b"):
        out = str(tmp_path_factory.mktemp(f"campaign_{tag}"))
        runner_mod.run_campaign(MICRO_SPEC, out, log=lambda m: None)
        dirs.append(out)
    return dirs


def test_runner_parent_never_imports_jax(tmp_path):
    """A point's process may need the chip, which one process holds
    at a time: the campaign parent must stay off JAX entirely."""
    import subprocess
    import sys
    spec = dict(MICRO_SPEC, axes={"fan_in": [2]})
    code = (
        "import json, sys\n"
        "from shadow_tpu.sweep import runner\n"
        f"runner.run_campaign(json.loads({json.dumps(spec)!r}), "
        f"{str(tmp_path)!r}, log=lambda m: None)\n"
        "assert 'jax' not in sys.modules, 'runner parent imported jax'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_two_run_dataset_byte_identity(micro_campaign):
    da = ds_mod.aggregate(MICRO_SPEC, micro_campaign[0])
    db = ds_mod.aggregate(MICRO_SPEC, micro_campaign[1])
    assert da.to_bytes() == db.to_bytes()
    # and aggregation itself is pure: same inputs, same bytes again
    assert da.to_bytes() == ds_mod.aggregate(
        MICRO_SPEC, micro_campaign[0]).to_bytes()


def test_aggregator_conservation(micro_campaign):
    from shadow_tpu.trace.events import iter_fct_records, split_fabric
    from shadow_tpu.trace.fabricstat import receiver_rows
    ds = ds_mod.aggregate(MICRO_SPEC, micro_campaign[0])
    points = spec_mod.expand(MICRO_SPEC)
    assert len(ds.meta["points"]) == len(points) == 2
    for i, p in enumerate(points):
        pdir = os.path.join(micro_campaign[0], p["point_id"])
        with open(os.path.join(pdir, "fabric-sim.bin"), "rb") as f:
            _fb, fct = split_fabric(f.read())
        chan_rows = receiver_rows(list(iter_fct_records(fct)))
        # THE conservation gate: dataset flow count == FCT channel
        # receiver-vantage rows, for every point
        assert ds.meta["points"][i]["counts"]["flows"] \
            == len(chan_rows) == len(ds.point_flows(i))
        # fan-in N sinks N download flows
        assert len(chan_rows) == p["axes"]["fan_in"]
        # per-point quantiles are ordered (monotone-sane)
        q = ds.meta["points"][i]["quantiles"]
        assert q["p50_ns"] <= q["p99_ns"] <= q["p999_ns"]
    assert len(ds.meta["tail_curves"]) == 2


def test_aggregator_fails_closed(micro_campaign, tmp_path):
    """A flow-count mismatch (corrupt point summary) or conservation
    violation must raise, never silently aggregate."""
    import shutil
    out = tmp_path / "corrupt"
    shutil.copytree(micro_campaign[0], out)
    p0 = spec_mod.expand(MICRO_SPEC)[0]
    pj = out / p0["point_id"] / "point.json"
    data = json.loads(pj.read_text())
    data["flows"] += 1
    pj.write_text(json.dumps(data))
    with pytest.raises(ds_mod.DatasetError, match="flow count"):
        ds_mod.aggregate(MICRO_SPEC, str(out))
    data["flows"] -= 1
    data["conservation"] = "2 violations"
    pj.write_text(json.dumps(data))
    with pytest.raises(ds_mod.DatasetError, match="conservation"):
        ds_mod.aggregate(MICRO_SPEC, str(out))


def test_dataset_round_trip(micro_campaign, tmp_path):
    ds = ds_mod.aggregate(MICRO_SPEC, micro_campaign[0])
    path = str(tmp_path / "micro.swds")
    ds.write(path)
    loaded = ds_mod.load(path)
    assert loaded.to_bytes() == ds.to_bytes()
    assert loaded.meta == ds.meta
    assert [loaded.point_flows(i) for i in range(2)] \
        == [ds.point_flows(i) for i in range(2)]
    # truncation and wrong magic are refused
    blob = ds.to_bytes()
    (tmp_path / "trunc.swds").write_bytes(blob[:-10])
    with pytest.raises(ds_mod.DatasetError, match="truncated"):
        ds_mod.load(str(tmp_path / "trunc.swds"))
    (tmp_path / "bad.swds").write_bytes(b"\x00" * 64)
    with pytest.raises(ds_mod.DatasetError, match="magic|not a"):
        ds_mod.load(str(tmp_path / "bad.swds"))


def test_per_flow_mark_rate_in_dataset(micro_campaign):
    """The FCT records the dataset carries have the marks column
    (ISSUE 12 satellite: per-flow ECN mark-rate telemetry)."""
    ds = ds_mod.aggregate(MICRO_SPEC, micro_campaign[0])
    for row in ds.point_flows(0):
        assert len(row) == 11  # ..., rtx, marks
        assert row[10] >= 0


# ---------------------------------------------------------------------
# ckpt fork semantics
# ---------------------------------------------------------------------

def test_ckpt_fork_allows_k_and_refuses_cc(tmp_path):
    from shadow_tpu.ckpt.fork import check_fork_compatible, fork_diff
    from shadow_tpu.ckpt.format import CkptError
    from shadow_tpu.sweep.point import build_config
    from shadow_tpu.tools.netgen import incast_yaml

    text = incast_yaml(2, nbytes=40_000, stop_time="800ms")
    base = build_config(text, {"dctcp_k_pkts": 20,
                               "dctcp_k_bytes": 30_000}, 0)
    k_var = build_config(text, {"dctcp_k_pkts": 5,
                                "dctcp_k_bytes": 7_500}, 0)
    assert check_fork_compatible(base, k_var) == [
        "experimental.dctcp_k_bytes", "experimental.dctcp_k_pkts"]
    # stop_time is fork-safe too
    longer = build_config(text, None, 0)
    longer.general.stop_time_ns = 2_000_000_000
    assert check_fork_compatible(base, longer) == [
        "general.stop_time"]
    # cc changes are refused with the dedicated message
    cc_var = build_config(
        incast_yaml(2, nbytes=40_000, stop_time="800ms",
                    tcp={"cc": "dctcp", "ecn": "on"}), None, 0)
    with pytest.raises(CkptError, match="cc/ecn.*not byte-compat"):
        check_fork_compatible(base, cc_var)
    # any other semantic change is refused naming the keys
    seed_var = build_config(
        incast_yaml(2, nbytes=40_000, stop_time="800ms", seed=99),
        None, 0)
    with pytest.raises(CkptError, match="general.seed"):
        check_fork_compatible(base, seed_var)
    assert "general.seed" in fork_diff(base, seed_var)


# ---------------------------------------------------------------------
# Surrogate: frozen micro-dataset, no sim
# ---------------------------------------------------------------------

def _frozen_samples():
    """A deterministic synthetic 2-point micro-dataset in sample
    form: 2 links, a handful of flows each, targets with a size ->
    FCT correlation for the loss to learn."""
    samples = []
    for pi in range(2):
        n_flows = 4 + pi
        flow_feats = np.array(
            [[4.0 + 0.2 * i, float(pi % 2), 1.0, 1.0, 0.5, 2.0]
             for i in range(n_flows)], np.float32)
        samples.append({
            "point_id": f"frozen{pi}",
            "features": {"fan_in": 2 + pi, "cc": "reno",
                         "dctcp_k": 20, "load": 1.0, "n_leaf": 0},
            "link_feats": np.array([[7.0, 4.0, 0.0], [7.5, 3.0, 1.0]],
                                   np.float32),
            "flow_feats": flow_feats,
            "pairs": np.array([[i, i % 2] for i in range(n_flows)],
                              np.int32),
            "flow_t": np.array([6.0 + 0.3 * i
                                for i in range(n_flows)], np.float32),
            "link_t": np.array([1.5, 0.0], np.float32),
            "link_mask": np.array([1.0, 0.0], np.float32),
        })
    return samples


def test_surrogate_forward_shape_and_determinism():
    from shadow_tpu.surrogate import model
    p1 = model.init_params(7)
    p2 = model.init_params(7)
    for k in p1:
        for kk in p1[k]:
            assert (p1[k][kk] == p2[k][kk]).all(), (k, kk)
    assert any((model.init_params(8)[k][kk] != p1[k][kk]).any()
               for k in p1 for kk in p1[k])
    s = _frozen_samples()[0]
    f1, l1 = model.forward(p1, s)
    f2, l2 = model.forward(p1, s)
    assert f1.shape == (s["flow_feats"].shape[0],)
    assert l1.shape == (s["link_feats"].shape[0],)
    assert (np.asarray(f1) == np.asarray(f2)).all()
    assert (np.asarray(l1) == np.asarray(l2)).all()
    assert np.isfinite(np.asarray(f1)).all()


def test_surrogate_loss_decreases_on_frozen_micro_dataset():
    from shadow_tpu.surrogate import train
    samples = _frozen_samples()
    params, hist = train.train(samples, seed=3, steps=40, log=None)
    assert hist[-1] < hist[0], (hist[0], hist[-1])
    tab = train.error_table(params, samples)
    for name in ("p50", "p99", "p999"):
        assert tab[f"mean_rel_err_{name}"] is not None
    assert len(tab["points"]) == 2


def test_surrogate_features_from_campaign(micro_campaign):
    """Featurization of a REAL campaign: paths resolve over the
    recorded topology, every flow gets a non-empty path, targets are
    finite."""
    from shadow_tpu.surrogate import features
    ds = ds_mod.aggregate(MICRO_SPEC, micro_campaign[0])
    samples = features.build_samples(ds)
    assert len(samples) == 2
    for s, p in zip(samples, spec_mod.expand(MICRO_SPEC)):
        assert s["flow_feats"].shape[0] == p["axes"]["fan_in"]
        assert s["pairs"].shape[0] >= s["flow_feats"].shape[0]
        assert np.isfinite(s["flow_t"]).all()
        assert s["link_mask"].sum() >= 1  # the sink queue was seen


# ---------------------------------------------------------------------
# Warm start (slow: ramp + fork + resume subprocesses)
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_warm_started_point_end_to_end(tmp_path):
    """warm_start: one ramp per fork group, forked per dctcp_k
    variant, each point RESUMED from its forked archive — and the
    dataset aggregates with conservation intact, recording
    warm_started honestly."""
    spec = {
        "name": "warm", "scenario": "incast",
        "base": {"nbytes": 60_000, "stop_time": "1200ms",
                 "fan_in": 3},
        "axes": {"dctcp_k": [5, 20], "cc": ["dctcp"]},
        "warm_start": {"at_ms": 400},
        "time_limit_s": 240,
    }
    out = str(tmp_path / "campaign")
    manifest = runner_mod.run_campaign(spec, out, log=lambda m: None)
    assert len(manifest) == 2
    assert all(ent["warm_started"] for ent in manifest.values())
    # both points share ONE ramp directory with ONE snapshot
    ramps = [d for d in os.listdir(out) if d.startswith("ramp.")]
    assert len(ramps) == 1
    # the resumed points produced forked archives + full channels
    for pid, ent in manifest.items():
        assert os.path.exists(os.path.join(ent["dir"], "warm.stck"))
        pj = json.loads(open(os.path.join(ent["dir"],
                                          "point.json")).read())
        assert pj["resumed"] and pj["conservation"] == "ok"
    ds = ds_mod.aggregate(spec, out)
    assert all(p["warm_started"] for p in ds.meta["points"])
    # the K=5 variant marks at least as much as K=20 (same traffic,
    # lower threshold) — the forked knob demonstrably took effect
    marked = {p["axes"]["dctcp_k"]: p["marked_pkts"]
              for p in ds.meta["points"]}
    assert marked[5] >= marked[20]
    assert marked[5] > 0


# ---------------------------------------------------------------------
# Self-healing fleet (docs/ROBUSTNESS.md "Self-healing sweeps")
# ---------------------------------------------------------------------

def test_self_healing_spec_validation():
    ok = spec_mod.validate_spec(dict(MICRO_SPEC, retries=2,
                                     max_failed_points=1))
    assert ok["retries"] == 2 and ok["max_failed_points"] == 1
    # defaults
    base = spec_mod.validate_spec(MICRO_SPEC)
    assert base["retries"] == 1 and base["max_failed_points"] == 0
    with pytest.raises(spec_mod.SpecError, match="retries"):
        spec_mod.validate_spec(dict(MICRO_SPEC, retries=-1))
    with pytest.raises(spec_mod.SpecError, match="max_failed_points"):
        spec_mod.validate_spec(dict(MICRO_SPEC,
                                    max_failed_points=True))


def test_failed_point_recorded_then_resume_heals(tmp_path,
                                                 monkeypatch):
    """One point forced to fail: the campaign completes (budget 1),
    the manifest and the .swds dataset record the failure honestly,
    and `--resume` re-runs ONLY the missing point to a full dataset
    byte-identical to an untouched campaign's."""
    spec = dict(MICRO_SPEC, retries=0, max_failed_points=1)
    points = spec_mod.expand(spec)
    victim = points[1]["point_id"]
    real_run_sub = runner_mod._run_sub
    ran: list = []

    def sabotaged(task, task_path, log_path, tl):
        ran.append(os.path.basename(os.path.dirname(task_path)))
        if victim in task_path:
            raise runner_mod.PointFailure("injected failure")
        return real_run_sub(task, task_path, log_path, tl)

    monkeypatch.setattr(runner_mod, "_run_sub", sabotaged)
    out = str(tmp_path / "camp")
    manifest = runner_mod.run_campaign(spec, out, log=lambda m: None)
    assert manifest[victim]["status"] == "failed"
    assert "injected failure" in manifest[victim]["error"]
    disk = json.load(open(os.path.join(out, "manifest.json")))
    assert disk["failed_points"] == [victim]
    # Partial-but-honest dataset: the failed point is metadata, not a
    # hole.
    ds = ds_mod.aggregate(spec, out)
    assert [fp["point_id"] for fp in ds.meta["failed_points"]] == \
        [victim]
    assert len(ds.meta["points"]) == len(points) - 1

    # Resume with the sabotage lifted: only the victim re-runs.
    monkeypatch.setattr(runner_mod, "_run_sub", real_run_sub)
    ran_before = list(ran)
    manifest2 = runner_mod.run_campaign(spec, out, log=lambda m: None,
                                        resume=True)
    assert ran == ran_before  # the patched recorder saw nothing new
    assert all(ent["status"] == "ok" for ent in manifest2.values())
    ds2 = ds_mod.aggregate(spec, out)
    assert ds2.meta["failed_points"] == []
    assert len(ds2.meta["points"]) == len(points)
    # The healed dataset is byte-identical to a clean campaign's
    # (identity-safe subprocesses: bytes depend only on the spec).
    clean = str(tmp_path / "clean")
    runner_mod.run_campaign(spec, clean, log=lambda m: None)
    assert ds2.to_bytes() == ds_mod.aggregate(spec, clean).to_bytes()


def test_max_failed_points_budget_aborts(tmp_path, monkeypatch):
    """Failures past the budget abort the campaign loudly."""
    spec = dict(MICRO_SPEC, retries=0, max_failed_points=0)

    def always_fail(task, task_path, log_path, tl):
        raise runner_mod.PointFailure("boom")

    monkeypatch.setattr(runner_mod, "_run_sub", always_fail)
    with pytest.raises(runner_mod.PointFailure,
                       match="max_failed_points"):
        runner_mod.run_campaign(spec, str(tmp_path / "camp"),
                                log=lambda m: None)


def test_all_points_failed_aggregate_refuses(tmp_path, monkeypatch):
    spec = dict(MICRO_SPEC, retries=0, max_failed_points=10)

    def always_fail(task, task_path, log_path, tl):
        raise runner_mod.PointFailure("boom")

    monkeypatch.setattr(runner_mod, "_run_sub", always_fail)
    out = str(tmp_path / "camp")
    runner_mod.run_campaign(spec, out, log=lambda m: None)
    with pytest.raises(ds_mod.DatasetError, match="every campaign"):
        ds_mod.aggregate(spec, out)
