"""A CPU rehearsal of chipbench's window and comparison, and its refusals.

The harness's look for a chip is skipped by calling ``run_cell`` from
the test: the rest of a run (inputs from the seed, the warm block, the
window through ``Simulator.run`` with the outputs its traffic asks for,
the comparison with the float64 reference and the outputs' checks)
runs at a tiny size. Then the timed path is broken underneath, once
for each fault the cells can have, and ``correct`` has to come out
false; and the control (the reference with bfloat16 force arithmetic
in the program's place, ``chip_control.py``, which runs it at a cell's
own size on the chip) has to fail the limit. A cell added as files
alone (a scenario, a configuration naming it, a traffic mix with every
output on) runs from a directory of its own.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, registry
from chipbench.reference import window as ref

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chip_control  # noqa: E402

N = 256
CELLS = {"solo": "plummer-262k.solo", "ring": "plummer-1m.ring4",
         "monitored": "plummer-262k.monitored",
         "files_alone": "cube-256.all_outputs"}
OUTPUT_CHECKS = {"trajectory_frames_off", "trajectory_last_frame_diff",
                 "checkpoint_faults"}
# A scenario the benchmark does not have: a uniform cube at rest, with
# masses drawn from the key.
TOY_SCENARIO = '''
import jax
import jax.numpy as jnp


def make(key, cfg):
    n = cfg["simulation"]["n"]
    k1, k2 = jax.random.split(key)
    pos = jax.random.uniform(k1, (n, 3), jnp.float32, -1.0, 1.0) * cfg["side"]
    m = jax.random.uniform(k2, (n,), jnp.float32, 0.5, 1.5) * cfg["mass"]
    return pos, jnp.zeros((n, 3), jnp.float32), m
'''


def _files_alone(base):
    """A copy of the benchmark's files with one cell added as files
    alone: a scenario module, a configuration that names it, the
    ``window`` reference and the ``direct`` work, a traffic mix with
    every output on and the cell's workload file."""
    shutil.copytree(registry.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (base / "scenarios" / "toy_cube.py").write_text(TOY_SCENARIO)
    sim = dict(registry.cell(CELLS["solo"])["config_data"]["simulation"],
               n=N)
    (base / "configs" / "cube-256.json").write_text(json.dumps({
        "ics": "toy_cube", "check": "window", "work": "direct",
        "side": 1e12, "mass": 4e27,
        "n": N, "simulation": sim,
        "force_evals": {"per_step": 1, "per_run": 1}, "reduced": []}))
    (base / "traffic" / "all_outputs.json").write_text(json.dumps({
        "io_pipeline": "auto", "trajectory_every": 5,
        "checkpoint_every": 10}))
    (base / "workloads" / f"{CELLS['files_alone']}.json").write_text(
        json.dumps({"config": "cube-256", "traffic": "all_outputs",
                    "chips": 1, "why": "a test cell", "block_steps": 5,
                    "nominal_step_s": 1.0, "sample_targets": 64,
                    "limits": {"state_gap": 0.01}}))
    return str(base)


def _cell(kind, tmp=None):
    base = registry.HERE
    if kind == "files_alone":
        base = _files_alone(tmp / "chipbench")
    cell = registry.cell(CELLS[kind], base=base)
    cell["sample_targets"] = 64
    # One window of a few whole blocks at the tiny size.
    cell["nominal_step_s"] = 1.0
    return cell


def _over(kind, **extra):
    over = {"n": N, **extra}
    if kind == "ring":
        over["mesh_shape"] = [4]
    return over


def _run(kind, tmp, seed=2**33 + 7, **extra):
    cell = _cell(kind, tmp)
    return harness.run_cell(
        cell, seed=seed, seconds=2 * cell["block_steps"], trace=False,
        t0=time.perf_counter(), sim_overrides=_over(kind, **extra),
        out_dir=str(tmp / "out"))


@pytest.mark.parametrize("kind", ["solo", "ring", "monitored", "files_alone"])
def test_rehearsal_is_correct(kind, tmp_path):
    out = _run(kind, tmp_path)
    assert out["correct"] is True, out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "window", "checks"]
    assert set(out["metrics"]) == {"step_s", "setup_s"}
    assert out["metrics"]["step_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    gap = out["checks"]["state_gap"]
    assert gap["value"] < gap["limit"] / 10
    assert out["attempted"] == out["window"]["steps"]
    outputs = kind in ("monitored", "files_alone")
    assert set(out["checks"]) == {"state_gap"} | (
        OUTPUT_CHECKS if outputs else set())
    assert all(out["checks"][k]["limit"] == 0 for k in OUTPUT_CHECKS
               if outputs)
    # The outputs' directory is gone after the check.
    assert not os.path.exists(tmp_path / "out" / CELLS[kind])


def _wrap_accel(monkeypatch, wrap):
    """Break the force every timed program calls, underneath the
    Simulator (its block and its opening evaluation both late-bind
    ``_accel2``)."""
    from gravity_tpu.simulation import Simulator

    orig = Simulator._build_fns

    def build(self):
        orig(self)
        inner = self._accel2
        self._accel2 = lambda p, m: wrap(inner, p, m)

    monkeypatch.setattr(Simulator, "_build_fns", build)


def _half_the_sources(inner, p, m):
    keep = (jnp.arange(m.shape[0]) % 2 == 0).astype(m.dtype)
    return inner(p, 2 * m * keep)


def _one_answer_altered(inner, p, m):
    body = int(ref.sample_targets(2**33 + 7, N, 64)[5])
    scale = jnp.where(jnp.arange(m.shape[0]) == body, 1.5, 1.0)
    return inner(p, m) * scale.astype(p.dtype)[:, None]


def _frame_altered(record):
    """A trajectory writer's ``record`` that moves one value of every
    frame by one ulp on its way to the file."""
    import numpy as np

    def altered(self, step, positions):
        positions = np.array(positions)
        positions[0, 0] = np.nextafter(positions[0, 0], np.float32(np.inf))
        return record(self, step, positions)

    return altered


@pytest.mark.parametrize("kind,fault", [
    ("solo", "state_unchanged"), ("ring", "state_unchanged"),
    ("solo", "half_the_sources"), ("ring", "half_the_sources"),
    ("solo", "one_answer_altered"), ("ring", "one_answer_altered"),
    ("ring", "no_exchange"),
    ("monitored", "state_unchanged"), ("monitored", "half_the_sources"),
    ("monitored", "one_answer_altered"),
    ("monitored", "trajectory_off"), ("monitored", "checkpoint_left_out"),
    ("monitored", "frame_altered"),
])
def test_faults_read_not_correct(monkeypatch, tmp_path, kind, fault):
    import gravity_tpu.simulation as simulation
    from gravity_tpu.simulation import Simulator

    if fault == "state_unchanged":
        monkeypatch.setattr(simulation, "make_step_fn",
                            lambda *a, **k: (lambda st, acc: (st, acc)))
    elif fault == "half_the_sources":
        _wrap_accel(monkeypatch, _half_the_sources)
    elif fault == "one_answer_altered":
        _wrap_accel(monkeypatch, _one_answer_altered)
    elif fault == "no_exchange":
        monkeypatch.setattr(jax.lax, "ppermute",
                            lambda x, axis_name, perm: x)
    elif fault == "trajectory_off":
        # The writer the harness hands over never reaches the run.
        run = Simulator.run
        monkeypatch.setattr(
            Simulator, "run",
            lambda self, *a, trajectory_writer=None, **k: run(self, *a, **k))
    elif fault == "checkpoint_left_out":
        import gravity_tpu.utils.checkpoint as checkpoint

        monkeypatch.setattr(checkpoint, "save_checkpoint",
                            lambda *a, **k: None)
    elif fault == "frame_altered":
        from gravity_tpu.utils.trajectory import TrajectoryWriter

        monkeypatch.setattr(TrajectoryWriter, "record",
                            _frame_altered(TrajectoryWriter.record))
    out = _run(kind, tmp_path)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("kind", ["solo", "ring", "monitored"])
def test_control_reads_not_correct(kind):
    """The reference with bfloat16 force arithmetic, put in the
    program's place, fails the cell's limit (at this size too)."""
    cell = _cell(kind)
    gaps = chip_control.control_ref_bf16(
        cell, 2**33 + 7, 2 * cell["block_steps"], sim_overrides={"n": N})
    assert gaps["state_gap"] > cell["limits"]["state_gap"]


def test_program_bf16_path_reads_not_correct(tmp_path):
    """The program's own lower-precision path, switched on, fails."""
    out = _run("solo", tmp_path, dtype="bfloat16")
    assert out["correct"] is False, out["checks"]


def test_window_starts_from_the_benchmark_inputs(monkeypatch, tmp_path):
    """A warm block that corrupts the state it ends on cannot feed the
    window: the window starts from the benchmark's initial conditions
    again, so the run stays correct."""
    from gravity_tpu.simulation import Simulator

    orig = Simulator.run
    calls = []

    def run(self, *a, **k):
        out = orig(self, *a, **k)
        calls.append(1)
        if len(calls) == 1:  # the warm block
            st = self.state
            self.state = type(st)(st.positions * 1.01, st.velocities,
                                  st.masses)
        return out

    monkeypatch.setattr(Simulator, "run", run)
    assert _run("solo", tmp_path)["correct"] is True
    assert len(calls) == 2


def test_active_set_holds_targets_and_their_neighbours():
    import numpy as np

    x0 = np.array([[0.0, 0, 0], [1.0, 0, 0], [50.0, 0, 0], [0, 30.0, 0]])
    v0 = np.zeros_like(x0)
    v0[3, 1] = 1.0  # 2 * 1 * T = 10 more reach
    act = ref.active_set(x0, v0, np.array([0]), eps=1.0, window_s=5.0)
    assert act.tolist() == [0, 1, 3]


# A work module the benchmark does not have: fixed numbers.
TOY_WORK = '''
def per_eval(cfg, x0, masses):
    return {"flops": 1.5e12 * cfg["toy_work"], "bytes": 4.0e9,
            "bound_note": "a test count"}
'''


def test_work_named_by_the_configuration_reaches_the_metrics(tmp_path):
    """A configuration that names a new work module (added as a file
    alone) has the metrics read that module's count, times the
    window's evaluations, with no harness edit."""
    from chipbench.trace import WINDOW_END, WINDOW_START, DeviceTrace

    base = _files_alone(tmp_path / "chipbench")
    with open(os.path.join(base, "work", "toy_work.py"), "w") as fh:
        fh.write(TOY_WORK)
    path = os.path.join(base, "configs", "cube-256.json")
    with open(path) as fh:
        cfg = json.load(fh)
    with open(path, "w") as fh:
        json.dump(dict(cfg, work="toy_work", toy_work=2.0), fh)
    cell = dict(registry.cell(CELLS["files_alone"], base=base),
                sample_targets=64, nominal_step_s=1.0)
    out = harness.run_cell(
        cell, seed=11, seconds=2 * cell["block_steps"], trace=True,
        t0=time.perf_counter(), sim_overrides=_over("files_alone"),
        trace_dir=str(tmp_path / "trace"), out_dir=str(tmp_path / "out"),
        peaks={"peak_flops": 1e12, "hbm_bytes_per_s": 1e9})
    assert out["correct"] is True, out["checks"]
    assert out["window"]["work"] == {
        "flops_per_eval": 3e12, "bytes_per_eval": 4e9,
        "note": "a test count", "bound": "memory"}
    # What a metric reads of such a run: the count times the evaluations.
    evals = out["window"]["force_evals"]
    flops, nbytes = 3e12 * evals, 4e9 * evals
    tr = DeviceTrace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [WINDOW_START, 0, 2e9, {}], [WINDOW_END, 2e9, 0, {}]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["_nbody_kernel", 0, 1e9, {}]]}]}]})
    f = harness.Facts(n=N, chips=1, evals=evals, window_s=2.0, stats={},
                      peaks={"peak_flops": 1e12, "hbm_bytes_per_s": 1e9},
                      trace=tr, flops=flops, bytes=nbytes)
    mods = registry.metric_modules(base=base)
    assert mods["forces_roofline"].read(f) == pytest.approx(
        100 * max(flops / 1e12, nbytes / 1e9) / 1.0)
    assert mods["step_mfu"].read(f) == pytest.approx(100 * flops / 2e12)


def test_a_configuration_without_work_is_refused(tmp_path):
    cell = _cell("solo")
    cell["config_data"] = {k: v for k, v in cell["config_data"].items()
                           if k != "work"}
    with pytest.raises(KeyError):
        harness.run_cell(cell, seed=1, seconds=2 * cell["block_steps"],
                         trace=False, t0=time.perf_counter(),
                         sim_overrides=_over("solo"),
                         out_dir=str(tmp_path / "out"))


def test_main_refuses_a_device_that_is_not_a_tpu(capsys):
    rc = harness.main(["--workload", "plummer-262k.solo", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc != 0 and cap.out == ""
    assert "TPU" in cap.err


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """In a directory holding only BENCHMARK.json and the files under
    ``paths``, the command exits non-zero and prints no result."""
    bench = registry.benchmark()
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(registry.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    cmd = [sys.executable] + bench["command"][1:] + [
        "--workload", "plummer-262k.solo", "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and r.stdout == ""


@pytest.mark.parametrize("seconds,block,nominal,expect", [
    (10, 10, 0.3926, 30), (10, 2, 1.5445, 6), (0.1, 10, 0.39, 10),
    (20, 10, 0.3926, 50)])
def test_window_steps_are_whole_blocks(seconds, block, nominal, expect):
    assert harness.window_steps(seconds, block, nominal) == expect


def test_sample_is_drawn_from_the_seed():
    a = ref.sample_targets(2**33 + 1, 1000, 64)
    assert (a == ref.sample_targets(2**33 + 1, 1000, 64)).all()
    assert not (a == ref.sample_targets(1, 1000, 64)).all()
    assert len(set(a.tolist())) == 64


def test_result_line_is_json_serialisable(tmp_path):
    out = _run("monitored", tmp_path, seed=5)
    json.loads(json.dumps(out))
