"""The driver's phases as profiler spans (``telemetry/tracing.span``).

A small ``Simulator.run`` under ``jax.profiler``, its capture read back
with the benchmark's own reader (``chipbench.trace.load_xplane``): one
``gravity.run`` holding one open, drain and close, and one dispatch,
wait and consume per block, with the pipeline on and off. And the
helper itself: one call feeds the profiler and the JSONL stream under
one name on one clock, and formats nothing while no profiler runs.
"""

import jax
import pytest

from chipbench.trace import load_xplane, newest_xplane
from gravity_tpu.config import SimulationConfig
from gravity_tpu.simulation import Simulator
from gravity_tpu.telemetry import SPAN_NAMES, Telemetry, Tracer, load_spans
from gravity_tpu.telemetry import tracing

BLOCK_SPANS = ("gravity.block.dispatch", "gravity.block.wait",
               "gravity.block.consume")
RUN_SPANS = ("gravity.run", "gravity.run.open", "gravity.run.drain",
             "gravity.run.close", "gravity.run.final_state")


def _cfg(**kw):
    return SimulationConfig(model="random", n=32, steps=6, dt=3600.0,
                            integrator="leapfrog", force_backend="dense",
                            progress_every=2, **kw)


def _profiled(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, load_xplane(newest_xplane(str(log_dir)))


def _program_spans(trace):
    """[name, start, end, stats, line] of the capture's gravity.* spans,
    in start order; ``line`` tells the host threads apart."""
    return sorted(
        ([name, s, s + d, stats, (i, j)]
         for i, p in enumerate(trace["planes"])
         for j, line in enumerate(p["lines"])
         for name, s, d, stats in line["events"]
         if name.startswith("gravity.")),
        key=lambda sp: sp[1])


@pytest.mark.parametrize("io_pipeline", ["on", "off"])
def test_run_span_tree(tmp_path, io_pipeline):
    sim = Simulator(_cfg(io_pipeline=io_pipeline))
    sim.run()  # compile outside the capture
    _, trace = _profiled(tmp_path, sim.run)
    got = _program_spans(trace)
    names = [sp[0] for sp in got]
    for name in RUN_SPANS:
        assert names.count(name) == 1, names
    blocks = 3  # 6 steps in blocks of 2
    for name in BLOCK_SPANS:
        assert names.count(name) == blocks, names
    assert len(got) == len(RUN_SPANS) + blocks * len(BLOCK_SPANS)
    run = next(sp for sp in got if sp[0] == "gravity.run")
    assert all(run[1] <= sp[1] and sp[2] <= run[2] for sp in got)
    assert {sp[4] for sp in got} == {run[4]}  # one thread: the caller's
    close = next(sp for sp in got if sp[0] == "gravity.run.close")
    final = next(sp for sp in got if sp[0] == "gravity.run.final_state")
    assert close[1] <= final[1] and final[2] <= close[2]
    # Each block: dispatched, then waited on, then consumed, all
    # between the open and the drain.
    by = {n: [sp for sp in got if sp[0] == n] for n in BLOCK_SPANS}
    opened = next(sp for sp in got if sp[0] == "gravity.run.open")
    drain = next(sp for sp in got if sp[0] == "gravity.run.drain")
    for k in range(blocks):
        d, w, c = (by[n][k] for n in BLOCK_SPANS)
        assert int(d[3]["step"]) == int(w[3]["step"]) \
            == int(c[3]["step"]) == 2 * k
        assert int(d[3]["steps"]) == 2
        assert opened[2] <= d[1] < d[2] <= w[1] < w[2] <= c[1] < c[2] \
            <= drain[1]
    waits = [sp[1] for sp in by["gravity.block.wait"]]
    dispatches = [sp[1] for sp in by["gravity.block.dispatch"]]
    if io_pipeline == "on":
        # Block k+1 is in flight while block k is waited on.
        assert all(dispatches[k + 1] < waits[k] for k in range(blocks - 1))
    else:
        assert all(waits[k] < dispatches[k + 1] for k in range(blocks - 1))


def test_checkpoint_span_on_the_thread_that_saves(tmp_path):
    from gravity_tpu.utils.checkpoint import make_checkpoint_manager

    cfg = _cfg(io_pipeline="on", checkpoint_every=4,
               checkpoint_dir=str(tmp_path / "ckpt"))
    sim = Simulator(cfg)
    mgr = make_checkpoint_manager(cfg.checkpoint_dir)
    _, trace = _profiled(tmp_path / "prof",
                         lambda: sim.run(checkpoint_manager=mgr))
    got = _program_spans(trace)
    ckpt = [sp for sp in got if sp[0] == "gravity.checkpoint"]
    assert [int(sp[3]["step"]) for sp in ckpt] == [4]
    run = next(sp for sp in got if sp[0] == "gravity.run")
    assert ckpt[0][4] != run[4]  # the background writer's thread


def test_solo_trace_writes_the_phases_to_jsonl(tmp_path):
    tele = Telemetry(out_dir=str(tmp_path), worker="spans-w")
    stats = Simulator(_cfg()).run(telemetry=tele)
    names = [s["name"] for s in load_spans(str(tmp_path / "traces.jsonl"))
             if s["trace"] == stats["trace_id"]]
    for name in ("run", "run.open", "run.drain", "run.close"):
        assert names.count(name) == 1, names
    for name in ("block.dispatch", "block.wait", "block.consume", "block"):
        assert names.count(name) == 3, names
    assert set(names) <= set(SPAN_NAMES)


def test_one_call_feeds_both_sinks_on_one_clock(tmp_path):
    from jax.profiler import ProfileData

    tracer = Tracer(str(tmp_path / "traces.jsonl"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with tracing.span("run.open", tracer, "tr-1", step=3) as live:
            live["note"] = "late"
    finally:
        jax.profiler.stop_trace()
    (rec,) = tracer.read()
    assert (rec["name"], rec["trace"], rec["step"], rec["note"]) == (
        "run.open", "tr-1", 3, "late")
    pd = ProfileData.from_file(newest_xplane(str(tmp_path / "prof")))
    base = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                if "profile_start_time" in dict(p.stats))
    (ev,) = [e for p in pd.planes for line in p.lines for e in line.events
             if e.name == "gravity.run.open"]
    assert dict(ev.stats)["step"] == 3
    # The JSONL t0 and the annotation's start: the same wall clock.
    assert abs((base + ev.start_ns) / 1e9 - rec["t0"]) < 0.01


def test_tracer_span_is_the_helper(tmp_path):
    tracer = Tracer(str(tmp_path / "traces.jsonl"))
    with pytest.raises(ValueError):
        with tracer.span("round", "tr-2", parent="sp-0", job="j"):
            raise ValueError("boom")
    (rec,) = tracer.read()
    assert (rec["name"], rec["parent"], rec["job"]) == ("round", "sp-0", "j")
    assert rec["error"] == "ValueError: boom"
    # No tracer or no trace id: the profiler alone, nothing written.
    with tracing.span("round", tracer, None):
        pass
    with tracing.span("round"):
        pass
    assert len(tracer.read()) == 1


class _Counted:
    def __init__(self):
        self.n = 0

    def __str__(self):
        self.n += 1
        return "counted"

    __repr__ = __str__


def test_attrs_are_formatted_only_under_a_profiler(tmp_path):
    attr = _Counted()
    for _ in range(100):
        with tracing.span("block.wait", step=attr):
            pass
    assert attr.n == 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("block.wait", step=attr):
            pass
    finally:
        jax.profiler.stop_trace()
    assert attr.n >= 1

