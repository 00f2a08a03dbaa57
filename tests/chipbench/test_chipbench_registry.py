"""chipbench's parts are found by name, and BENCHMARK.json agrees with them."""

import json
import os
import re
import shutil

import pytest

from chipbench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def test_benchmark_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(registry.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


@pytest.mark.parametrize("cell", ["plummer-262k.solo", "plummer-1m.ring4",
                                  "plummer-262k.monitored"])
def test_cell_found_by_name(bench, cell):
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    c = registry.cell(cell)
    assert c["config"] == entry["config"] and c["chips"] == entry["chips"]
    assert c["traffic"] == entry["traffic"] and c["why"] == entry["why"]
    conf = next(x for x in bench["configs"] if x["name"] == c["config"])
    assert conf["file"] == f"chipbench/configs/{c['config']}.json"
    assert c["config_data"]["reduced"] == conf["reduced"]
    assert c["config_data"]["simulation"]["n"] == c["config_data"]["n"]
    assert set(c["limits"]) == {"state_gap"}
    # The scenario and the reference the configuration names.
    assert callable(registry.module(
        "scenarios", c["config_data"]["ics"]).make)
    assert callable(registry.module(
        "reference", c["config_data"]["check"]).integrate_targets)
    # The work of its force evaluation, by name.
    assert callable(registry.module("work", c["config_data"]["work"]).per_eval)


@pytest.mark.parametrize("config", sorted(
    fn[:-5] for fn in os.listdir(os.path.join(registry.HERE, "configs"))))
def test_every_configuration_names_its_parts(config):
    """Each configuration on disk, with a cell or waiting for one, names
    a scenario, a reference and a work module that exist."""
    c = registry._load("configs", config)
    assert c["simulation"]["n"] == c["n"]
    assert callable(registry.module("scenarios", c["ics"]).make)
    assert callable(registry.module("reference", c["check"]).integrate_targets)
    assert callable(registry.module("work", c["work"]).per_eval)


def test_metric_modules_match_benchmark(bench):
    mods = registry.metric_modules()
    layers = {}
    for m in bench["per_layer"]:
        mod = mods[m["name"]]
        assert (mod.UNIT, mod.BETTER, mod.MOVES, mod.SOURCE, mod.LAYER) == (
            m["unit"], m["better"], m["moves"], m["source"], m["layer"])
        layers.setdefault(mod.LAYER, set()).add(m["name"])
        cells = {w["name"] for w in bench["workloads"]}
        assert set(m.get("workloads", cells)) <= cells
    assert set(mods) == {m["name"] for m in bench["per_layer"]}
    assert registry.per_layer_for("plummer-262k.solo") == [
        "forces_roofline", "step_mfu", "device.idle_share",
        "device.outside_kernel_share",
        "driver.host_gap_share", "driver.window_compiles",
        "driver.open_idle_share", "driver.close_idle_share"]
    assert "driver.consume_idle_share" in registry.per_layer_for(
        "plummer-262k.monitored")


def test_a_cell_added_as_files_alone(tmp_path):
    """A new cell needs a workload file (and, if new, a config, a
    traffic file, a scenario and a reference) and nothing else: the
    harness finds each by name."""
    base = tmp_path / "chipbench"
    shutil.copytree(registry.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (base / "traffic" / "bursty.json").write_text(json.dumps(
        {"io_pipeline": "auto"}))
    (base / "scenarios" / "lattice.py").write_text(
        "def make(key, cfg):\n    return 'lattice'\n")
    assert registry.module("scenarios", "lattice",
                           base=str(base)).make(None, {}) == "lattice"
    with pytest.raises(FileNotFoundError):
        registry.module("scenarios", "lattice")
    (base / "workloads" / "plummer-262k.bursty.json").write_text(json.dumps(
        {"config": "plummer-262k", "traffic": "bursty", "chips": 1,
         "why": "a test cell", "block_steps": 5, "nominal_step_s": 0.4,
         "sample_targets": 64, "limits": {"state_gap": 0.01}}))
    c = registry.cell("plummer-262k.bursty", base=str(base))
    assert c["base"] == str(base)
    assert c["sample_targets"] == 64
    assert c["traffic_data"]["io_pipeline"] == "auto"
    assert c["config_data"]["n"] == 262144
    with pytest.raises(FileNotFoundError):
        registry.cell("plummer-262k.nothing", base=str(base))
    with pytest.raises(ValueError):
        registry.cell("../BENCHMARK", base=str(base))


# sha256 over the bytes of (positions, velocities, masses) that the
# benchmark's Plummer maker gave on the CPU before scenarios were found
# by name (``chipbench/ics.py::plummer``), at each configuration's size.
PLUMMER_DIGESTS = {
    ("plummer-262k", 2**33 + 7):
        "6f403890c18ce996c7692262adf062781a5e6f1daabba6193845872446addf1e",
    ("plummer-262k", 3240000001):
        "e55752606e340245783b5a0bb1ef11699c83b2f88a422f690cfdd26d8ad1687e",
    ("plummer-1m", 2**33 + 7):
        "fed4ba155e625d33e7b24403fb285c97381a4a7e74d97fe6609018af3e195f48",
    ("plummer-1m", 3240000001):
        "0d0923d342c9e5a4b46d085b273a00515dd688777c132fcd963e8a2e03d87c55",
}


@pytest.mark.parametrize("config,seed", sorted(PLUMMER_DIGESTS))
def test_plummer_ics_are_those_of_before_bit_for_bit(config, seed):
    import hashlib

    import numpy as np

    from chipbench import ics

    cfg = registry._load("configs", config)
    h = hashlib.sha256()
    for a in ics.make(cfg, seed):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == PLUMMER_DIGESTS[config, seed]


def test_peaks_known_and_unknown_device():
    p = registry.peaks("TPU v5 lite")
    assert p["peak_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.UnknownDevice):
        registry.peaks("TPU v9 imaginary")
    with pytest.raises(registry.UnknownDevice):
        registry.peaks("cpu")
