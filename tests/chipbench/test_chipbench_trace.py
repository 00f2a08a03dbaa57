"""chipbench's reduction from a profiler trace to per-layer metrics.

Hand-made traces in the shape ``trace.load_xplane`` gives (planes ->
lines -> events): busy union, idle share, which ops are the force
kernel and which are collectives, the roofline arithmetic, and the
breakdown's labels; and the same reduction on a trace the v5e recorded
(``fixtures/v5e_solo_trace.json.gz``: the device's ops and the
harness's spans of a ``--trace 1`` run of ``plummer-262k.solo``, seed
3000000003, PR 22), which has to give what that run printed.
"""

import gzip
import json
import os


import pytest

from chipbench import harness, registry
from chipbench.trace import WINDOW_END, WINDOW_START, DeviceTrace, minus, union

PEAKS = {"peak_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(devices, host=((WINDOW_START, 0, 100), (WINDOW_END, 100, 10))):
    """devices: per device, a list of (name, start, dur) ops."""
    planes = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [n, s, d, {}] for n, s, d in host]}]}]
    for i, ops in enumerate(devices):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [["jit_x", 0, 1000, {}]]},
            {"name": "XLA Ops", "events": [[n, s, d, {}] for n, s, d in ops]},
        ]})
    return {"planes": planes}


def _direct_work(n, evals):
    """(flops, bytes) of ``evals`` all-pairs evaluations, as the harness
    passes them: ``work/direct.py``'s count per evaluation, times
    ``evals``."""
    w = registry.module("work", "direct").per_eval({}, None, [0.0] * n)
    return w["flops"] * evals, w["bytes"] * evals


def _facts(tr, n=1000, evals=1, chips=1, stats=None, window_s=1.0):
    flops, nbytes = _direct_work(n, evals)
    return harness.Facts(n=n, chips=chips, evals=evals, window_s=window_s,
                         stats=stats or {}, peaks=PEAKS,
                         trace=DeviceTrace(tr), flops=flops, bytes=nbytes)


def _old_roofline(f, kernel_s):
    """``forces_roofline`` as it read before the work was named by the
    configuration: the all-pairs count written into the module."""
    flops = float(20) * f.n * (f.n - 1) * f.evals
    min_bytes = (16.0 + 12.0) * f.n * f.evals
    bound_s = max(flops / f.peaks["peak_flops"],
                  min_bytes / f.peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (f.chips * kernel_s)


def _old_step_mfu(f):
    work = float(20) * f.n * (f.n - 1) * f.evals
    window_s = f.trace.window_ns / 1e9
    return 100.0 * work / (window_s * f.chips * f.peaks["peak_flops"])


def _fixture_facts():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "v5e_solo_trace.json.gz")
    with gzip.open(path, "rt") as fh:
        tr = DeviceTrace(json.load(fh))
    flops, nbytes = _direct_work(262144, 81)
    return harness.Facts(n=262144, chips=1, evals=81, window_s=29.9294,
                         stats={"host_gap_frac": 2.8606042140165834e-06},
                         peaks=registry.peaks("TPU v5 lite"), trace=tr,
                         flops=flops, bytes=nbytes)


def test_union_and_minus():
    assert union([[5, 7], [0, 2], [1, 3], [7, 8], [9, 9]]) == [
        [0, 3], [5, 8]]
    assert minus([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert minus([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]
    assert minus([[0, 4]], [[-1, 5]]) == []


def test_busy_is_the_clipped_union_of_ops():
    tr = DeviceTrace(_trace([[("a", -20, 30), ("b", 5, 10), ("c", 50, 100)]]))
    assert tr.window == (0, 110)
    # a clips to [0, 10), b overlaps it to [0, 15), c clips to [50, 110).
    assert tr.busy_ns(0) == 75


def test_idle_share_and_breakdown_labels():
    tr = _trace([[("fusion.1", 0, 40), ("_nbody_kernel", 60, 40),
                  ("copy.1", 108, 12)]],
                host=((WINDOW_START, 0, 100), (WINDOW_END, 100, 20),
                      ("chipbench.inner", 40, 10),
                      ("gravity.run.final_state", 100, 6)))
    mods = registry.metric_modules()
    f = _facts(tr)
    assert mods["device.idle_share"].read(f) == pytest.approx(
        100 * (1 - 92 / 120))
    bd = f.trace.breakdown()
    assert bd["device_ops"][0][1] == pytest.approx(40e-9)
    # A gap is named by the innermost span over it, the program's
    # included: the one opened last.
    labels = [g[0] for g in bd["idle_gaps"]]
    assert labels == ["idle in chipbench.inner",
                      "idle in gravity.run.final_state"]


def test_roofline_counts_the_algorithm_not_the_kernel():
    mods = registry.metric_modules()
    # 20 * 1000 * 999 flop in 1e-3 s of kernel on each of 2 chips.
    tr = _trace([[("_nbody_kernel", 0, 1e6)]] * 2,
                host=((WINDOW_START, 0, 2e6), (WINDOW_END, 2e6, 1)))
    f = _facts(tr, chips=2)
    want = 100 * (20 * 1000 * 999 / 197e12) / (2 * 1e-3)
    assert mods["forces_roofline"].read(f) == pytest.approx(want)
    # The whole step's share: the same flops over the traced window.
    assert mods["step_mfu"].read(f) == pytest.approx(
        100 * 20 * 1000 * 999 / ((2e6 + 1) * 1e-9 * 2 * 197e12))
    # No kernel op found: nothing to read, never 0.
    empty = _facts(_trace([[("fusion.3", 0, 10)]]))
    assert mods["forces_roofline"].read(empty) is None


def test_exposed_collective_time():
    mods = registry.metric_modules()
    ops = [("collective-permute.1", 0, 30), ("_nbody_kernel", 20, 50),
           ("collective-permute.2", 90, 10)]
    f = _facts(_trace([ops, ops]), chips=2)
    # Exposed: [0, 20) and [90, 100) of a 110 ns window, on both chips.
    assert mods["ring.exposed_comm_share"].read(f) == pytest.approx(
        100 * 30 / 110)
    one = _facts(_trace([ops]))
    assert mods["ring.exposed_comm_share"].read(one) is None


def test_host_gap_share_reads_the_program_counter():
    mods = registry.metric_modules()
    f = _facts(_trace([[]]), stats={"host_gap_frac": 0.0125})
    assert mods["driver.host_gap_share"].read(f) == pytest.approx(1.25)
    assert mods["driver.host_gap_share"].read(_facts(_trace([[]]))) is None


def test_window_compiles_reads_the_harness_count():
    mods = registry.metric_modules()
    f = _facts(_trace([[]]))
    assert mods["driver.window_compiles"].read(f) == 0
    f.compiles = 7
    assert mods["driver.window_compiles"].read(f) == 7


def test_recorded_v5e_trace_reads_as_the_chip_run_printed():
    f = _fixture_facts()
    tr = f.trace
    mods = registry.metric_modules()
    got = {name: mods[name].read(f)
           for name in registry.per_layer_for("plummer-262k.solo")}
    # Not printed by that run (the metric is newer): see
    # test_outside_kernel_share.
    got.pop("device.outside_kernel_share")
    # The block loop's idle shares read None: the fixture predates the
    # program's spans.
    assert got == pytest.approx({
        "forces_roofline": 1.890912109227137,
        "step_mfu": 1.8880974458464963,
        "device.idle_share": 0.024447610602007508,
        "driver.host_gap_share": 0.00028606042140165834,
        "driver.window_compiles": 0,
        "driver.open_idle_share": None,
        "driver.close_idle_share": None}, rel=1e-12)
    assert tr.mean_busy_s() == pytest.approx(29.922397174, rel=1e-12)
    assert mods["ring.exposed_comm_share"].read(f) is None
    assert mods["driver.consume_idle_share"].read(f) is None
    assert tr.program_spans == []
    ops = dict(tr.breakdown()["device_ops"])
    assert ops["%pallas_accelerations_vs.7"] == pytest.approx(29.50223907)


@pytest.mark.parametrize("kind", ["fixture", "synthetic"])
def test_direct_work_reads_the_old_formula_bit_for_bit(kind):
    """The all-pairs count, moved from the two metric modules into
    ``work/direct.py`` and passed as ``Facts.flops``/``bytes``, gives
    every reading exactly what the modules computed before."""
    from chipbench.trace import FORCE_KERNEL, length

    mods = registry.metric_modules()
    if kind == "fixture":
        f = _fixture_facts()
    else:
        tr = _trace([[("_nbody_kernel", 0, 7.3e5), ("fusion", 8e5, 3e3)]] * 3,
                    host=((WINDOW_START, 0, 9e5), (WINDOW_END, 9e5, 17)))
        f = _facts(tr, n=262147, evals=83, chips=3)
    kernel_s = sum(length(f.trace.intervals(d, FORCE_KERNEL))
                   for d in range(len(f.trace.devices))) / len(
                       f.trace.devices) / 1e9
    assert mods["forces_roofline"].read(f) == _old_roofline(f, kernel_s)
    assert mods["step_mfu"].read(f) == _old_step_mfu(f)


def test_no_work_counted_reads_nothing():
    f = _facts(_trace([[("_nbody_kernel", 0, 50)]]))
    f.flops = f.bytes = None
    mods = registry.metric_modules()
    assert mods["forces_roofline"].read(f) is None
    assert mods["step_mfu"].read(f) is None


def test_outside_kernel_share():
    mods = registry.metric_modules()
    # Kernel [20, 70); other ops [0, 30) and [60, 90): outside the
    # kernel [0, 20) and [70, 90), 40 of a 110 ns window.
    ops = [("fusion.1", 0, 30), ("_nbody_kernel", 20, 50), ("sort.2", 60, 30)]
    f = _facts(_trace([ops, ops]), chips=2)
    assert mods["device.outside_kernel_share"].read(f) == pytest.approx(
        100 * 40 / 110)
    assert mods["device.outside_kernel_share"].read(
        _facts(_trace([[("fusion.1", 0, 30)]]))) is None
    # On the recorded solo run: busy time less the kernel's, about 1% of
    # the window (the integrator's ops between the kernel's calls).
    from chipbench.trace import FORCE_KERNEL, length

    f = _fixture_facts()
    kernel_ns = length(f.trace.intervals(0, FORCE_KERNEL))
    got = mods["device.outside_kernel_share"].read(f)
    assert got == pytest.approx(
        100 * (f.trace.busy_ns(0) - kernel_ns) / f.trace.window_ns,
        rel=1e-9)
    assert 0.05 < got < 2.0


def test_window_needs_the_harness_spans():
    with pytest.raises(ValueError):
        DeviceTrace(_trace([[]], host=(("other", 0, 1),)))
