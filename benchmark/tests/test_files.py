"""Every configuration, traffic mix, generator and per-layer metric is
a file of its own, found by the name BENCHMARK.json gives it, and every
cell's generated YAML parses through the program's ConfigOptions."""

import pytest

from harness import registry

BENCH = registry.benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def cell(name):
    return next(c for c in BENCH["workloads"] if c["name"] == name)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_found_by_name(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    cfg = registry.config(name)
    assert entry["file"] == f"benchmark/configs/{name}.json"
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    for key in ("assumed", "guarantees", "control", "rehearse", "agree"):
        assert cfg[key]
    registry.generator(cfg["generator"])
    assert callable(registry.reference(cfg["reference"]).compare)


@pytest.mark.parametrize("name", sorted({c["traffic"]
                                         for c in BENCH["workloads"]}))
def test_traffic_found_by_name(name):
    trf = registry.traffic(name)
    assert trf["name"] == name and trf["warmup"] and trf["horizon"]


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_found_by_name(name):
    assert callable(registry.metric_reader(name))


def test_unknown_names_are_errors():
    with pytest.raises(FileNotFoundError):
        registry.config("no-such-config")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no.such_metric")


def test_peaks_name_v5e():
    p = registry.peaks()["TPU v5 lite"]
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["source"] == "Google Cloud documentation, TPU v5e"


@pytest.mark.parametrize("rehearse", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_generated_yaml_parses(name, rehearse):
    from shadow_tpu.core.config import ConfigOptions

    import run
    c = cell(name)
    cfg = run.sizes_of(registry.config(c["config"]), rehearse)
    trf = registry.traffic(c["traffic"])
    exp = {**cfg["experimental"], **trf["experimental"]}
    gen = registry.generator(cfg["generator"])
    text = gen.make_yaml(cfg, trf, 2**31 + 12345, "tpu", exp)
    opts = ConfigOptions.from_yaml_text(text)
    assert opts.general.seed == 2**31 + 12345
    assert opts.experimental.scheduler == "tpu"
    assert len(opts.hosts) == gen.n_hosts(cfg)
