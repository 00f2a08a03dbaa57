"""Device-idle time charged to the innermost host span (``spans.py``).

Hand-made traces in the shape ``trace.load_xplane`` gives: the harness's
window spans, the program's ``gravity.*`` spans on the host's thread
lines, and the device's ops. Every idle nanosecond lands in exactly one
place, so the charges sum to the idle share; and the recorded v5e trace
(``fixtures/``), which predates the program's spans, reads None.
"""

import gzip
import json
import os

import pytest

from chipbench import registry, spans
from chipbench.harness import Facts
from chipbench import trace
from chipbench.trace import WINDOW_END, WINDOW_START, DeviceTrace

HARNESS = ((WINDOW_START, 0, 100), (WINDOW_END, 100, 10))
# gravity.run [5, 98) holds open [5, 30), a wait [60, 90) and the close
# [90, 98) with its final_state [92, 97).
PROGRAM = (("gravity.run", 5, 93), ("gravity.run.open", 5, 25),
           ("gravity.block.wait", 60, 30), ("gravity.run.close", 90, 8),
           ("gravity.run.final_state", 92, 5))
# Idle: [10, 40), [60, 99) and [105, 110) of the 110 ns window.
OPS = [("fusion.1", 0, 10), ("_nbody_kernel", 40, 20), ("copy.2", 99, 6)]


def _trace(devices, host=HARNESS, threads=(PROGRAM,)):
    """devices: per device, a list of (name, start, dur) ops; threads:
    per host thread line, its (name, start, dur) spans."""
    lines = [{"name": "python", "events": [[n, s, d, {}] for n, s, d in host]}]
    lines += [{"name": f"thread-{i}", "events": [
        [n, s, d, {}] for n, s, d in evs]} for i, evs in enumerate(threads)]
    planes = [{"name": "/host:CPU", "lines": lines}]
    for i, ops in enumerate(devices):
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Ops", "events": [[n, s, d, {}] for n, s, d in ops]},
        ]})
    return {"planes": planes}


def test_host_spans_reads_the_program_spans_alone():
    got = DeviceTrace(_trace([OPS])).program_spans
    assert [sp[0] for sp in got] == [sp[0] for sp in PROGRAM]
    assert got[0] == ["gravity.run", 5, 98]
    assert trace.host_events(_trace([OPS]), trace.HARNESS)[0] == [
        WINDOW_START, 0, 100]


def test_innermost_piece_by_piece():
    got = spans.innermost([list(sp) for sp in (
        ["a", 0, 10], ["b", 2, 5], ["c", 20, 30])])
    assert got == [[0, 2, "a"], [2, 5, "b"], [5, 10, "a"], [20, 30, "c"]]


def test_idle_charged_to_the_innermost_span():
    raw = _trace([OPS])
    dt = DeviceTrace(raw)
    prog = dt.program_spans
    (got,) = spans.idle_charges(dt, prog)
    assert got == {"gravity.run.open": 20, "gravity.run": 10,
                   "gravity.block.wait": 30, "gravity.run.close": 3,
                   "gravity.run.final_state": 5, None: 6}
    # With the harness's spans the uncovered time has a name too.
    (both,) = spans.idle_charges(dt, prog + dt.host_spans)
    assert both[WINDOW_START] == 1 and both[WINDOW_END] == 5
    assert None not in both


def test_charges_sum_to_the_idle_share():
    raw = _trace([OPS, OPS[:2] + [("copy.2", 98, 12)]])
    dt = DeviceTrace(raw)
    idle = registry.metric_modules()["device.idle_share"].read(Facts(
        n=1, chips=2, evals=1, window_s=1.0, stats={}, peaks={}, trace=dt))
    for sp in (dt.program_spans, dt.program_spans + dt.host_spans):
        assert sum(spans.shares(dt, sp).values()) == pytest.approx(
            idle, rel=1e-12)


def test_open_and_close_shares_mean_over_devices():
    raw = _trace([OPS, [("fusion.1", 0, 30)] + OPS[1:]])
    dt = DeviceTrace(raw)
    prog = dt.program_spans
    # Device 1 is busy through [10, 30): none of its idle is in open.
    assert spans.share(dt, prog, spans.OPEN) == pytest.approx(
        100 * (20 + 0) / 2 / 110)
    assert spans.share(dt, prog, spans.CLOSE) == pytest.approx(
        100 * (3 + 5) / 110)


def test_overlapping_threads_charge_the_later_span():
    """A checkpoint saved on a background thread during a consume: the
    span opened last is the one the host was last in."""
    threads = ((("gravity.run", 0, 100), ("gravity.block.consume", 10, 50)),
               (("gravity.checkpoint", 30, 50),))
    raw = _trace([[("k", 0, 10)]], threads=threads)
    dt = DeviceTrace(raw)
    (got,) = spans.idle_charges(dt, dt.program_spans)
    assert got == {"gravity.block.consume": 20, "gravity.checkpoint": 50,
                   "gravity.run": 20, None: 10}


def test_no_program_spans_reads_none():
    raw = _trace([OPS], threads=())
    dt = DeviceTrace(raw)
    assert spans.share(dt, dt.program_spans, spans.OPEN) is None
    assert spans.share(dt, dt.host_spans, spans.CLOSE) is None
    no_device = DeviceTrace(_trace([]))
    assert spans.share(no_device, no_device.program_spans,
                       spans.OPEN) is None


def test_recorded_v5e_trace_has_no_program_spans():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "v5e_solo_trace.json.gz")
    with gzip.open(path, "rt") as fh:
        raw = json.load(fh)
    dt = DeviceTrace(raw)
    assert dt.program_spans == []
    assert spans.share(dt, dt.program_spans, spans.OPEN) is None
    # Charged to the harness's spans alone, the idle still sums to
    # device.idle_share as that run printed it.
    assert sum(spans.shares(dt, dt.host_spans).values()) == pytest.approx(
        0.024447610602007508, rel=1e-9)


@pytest.mark.parametrize("metric,want", [
    ("driver.open_idle_share", 100 * 20 / 110),
    ("driver.close_idle_share", 100 * (3 + 5) / 110),
    # The consume [60, 80) and a checkpoint [80, 90) inside the wait.
    ("driver.consume_idle_share", 100 * (20 + 10) / 110),
])
def test_driver_idle_metrics_read_the_program_spans(metric, want):
    threads = (PROGRAM + (("gravity.block.consume", 60, 20),),
               (("gravity.checkpoint", 80, 10),))
    mod = registry.metric_modules()[metric]
    f = Facts(n=1, chips=1, evals=1, window_s=1.0, stats={}, peaks={},
              trace=DeviceTrace(_trace([OPS], threads=threads)))
    assert mod.read(f) == pytest.approx(want, rel=1e-12)
    f.trace = DeviceTrace(_trace([OPS], threads=()))
    assert mod.read(f) is None
