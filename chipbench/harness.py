"""The benchmark the driver runs: one cell, one seed, one process.

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Finds the cell's files by name (``registry``) and refuses to run,
   printing no result, unless JAX sees a TPU with the chips the cell
   asks for and ``peaks.json`` knows its kind.
2. Makes the initial conditions on the device from ``--seed`` by the
   scenario the configuration names (``ics.py``, ``scenarios/``) and
   builds ``Simulator(config, state)``. The traffic switches on the
   program's own outputs (``OUTPUTS``), written under ``.chipbench/out/``
   of the checkout.
3. Warms up with one block through ``Simulator.run`` itself: every
   program the window runs compiles (or loads from the persistent cache
   in ``.jax_cache/`` of the checkout) there, and counts as set-up.
   Then the simulator's state is put back to the initial conditions
   and the outputs emptied, so the window starts from what the
   benchmark made.
4. The window: one ``Simulator.run``, S whole blocks, ended by a
   value-fetch fence; the outputs are written inside it. S is fixed by
   ``--seconds`` and the cell's nominal step time, so every seed does
   the same work. With checkpoints on, the warm block and the window
   count their steps from the step before a checkpoint's (``first``),
   as a stretch of a longer run: the first block of each ends on a
   checkpoint, so the window holds one and the first save is warm.
5. With ``--trace 1``, counts the work of the window's force
   evaluations by the module the configuration names (``work/``), for
   the per-layer metrics to read.
6. Compares the window's final state with the plain reference the
   configuration names (``reference/``) at bodies drawn from the seed,
   and the outputs with the final state and with each other; prints
   each number compared beside its limit as the last lines of stderr,
   and the result as the last line of stdout.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import inspect
import json
import os
import shutil
import sys
import time

from chipbench import registry

CACHE_DIR = os.path.join(registry.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(registry.ROOT, ".chipbench", "trace")
OUT_DIR = os.path.join(registry.ROOT, ".chipbench", "out")
# The program's outputs a traffic file may switch on: positions every
# so many steps, a checkpoint every so many steps. Each is checked after
# the window, exactly (limit 0).
OUTPUTS = ("trajectory_every", "checkpoint_every")
REFERENCE_CPU_DEVICES = 4
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration")


def process_age_s() -> float:
    """Seconds since this process started (``/proc``; 0 if unreadable)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def window_steps(seconds: float, block: int, nominal_step_s: float) -> int:
    """Whole blocks that fill about ``seconds`` at the nominal step."""
    return block * max(1, round(seconds / (block * nominal_step_s)))


@dataclasses.dataclass
class Facts:
    """What a per-layer metric module may read of a run. ``flops`` and
    ``bytes`` are the work of the window's force evaluations as the
    configuration's ``work/<name>.py`` counts it (per evaluation, times
    ``evals``); None where it was not counted."""

    n: int
    chips: int
    evals: int
    window_s: float
    stats: dict
    peaks: dict
    compiles: int = 0
    trace: object = None
    flops: float | None = None
    bytes: float | None = None


def work_line(per_eval: dict, peaks: dict) -> dict:
    """The work of one force evaluation as the result line reports it,
    with the bound that holds at the published peaks."""
    line = {"flops_per_eval": per_eval["flops"],
            "bytes_per_eval": per_eval["bytes"],
            "note": per_eval["bound_note"]}
    if peaks:
        line["bound"] = ("compute" if per_eval["flops"] / peaks["peak_flops"]
                         >= per_eval["bytes"] / peaks["hbm_bytes_per_s"]
                         else "memory")
    return line


class _CompileCount:
    """Counts compiles and retraces while ``on`` (none belong in the
    window)."""

    def __init__(self):
        self.on, self.n = False, 0

    def __call__(self, event, duration=None, **kw):
        if self.on and event in _COMPILE_EVENTS:
            self.n += 1


def _use_cpu_devices_for_reference() -> None:
    """The CPU platform beside the TPU, for the float64 reference, with
    ``REFERENCE_CPU_DEVICES`` devices at most: XLA's CPU backend runs
    one op per device at a time, and more devices than that contend
    for the same cores (the 1M reference ran 54 s with 30 devices on
    the four-chip host). Before JAX starts; the TPU stays the default
    platform."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        n = min(REFERENCE_CPU_DEVICES, len(os.sched_getaffinity(0)) or 1)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def _enable_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, whatever the environment says, for every program."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device_line(devs, used) -> dict:
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _reference_kwargs(fn, sim: dict) -> dict:
    """The keys of the simulation dict that the reference's
    ``integrate_targets`` takes as keyword parameters (``steps`` is the
    window's, passed apart)."""
    return {k: sim[k] for k, p in inspect.signature(fn).parameters.items()
            if p.kind is p.KEYWORD_ONLY and k != "steps" and k in sim}


def _open_outputs(config, n: int) -> dict:
    """An empty output directory, and the writers ``Simulator.run``
    takes for the outputs ``config`` switches on, made as the program's
    CLI makes them."""
    shutil.rmtree(config.log_dir, ignore_errors=True)
    os.makedirs(config.log_dir)
    io = {}
    if config.record_trajectories:
        from gravity_tpu.utils.trajectory import TrajectoryWriter

        # every=1: the simulator strides the frames itself.
        io["trajectory_writer"] = TrajectoryWriter(
            os.path.join(config.log_dir, "trajectories"), n, every=1)
    if config.checkpoint_every:
        from gravity_tpu.utils.checkpoint import make_checkpoint_manager

        io["checkpoint_manager"] = make_checkpoint_manager(
            config.checkpoint_dir)
    return io


def _close_outputs(io: dict) -> None:
    if "checkpoint_manager" in io:
        io["checkpoint_manager"].close()


def _bits(a):
    import numpy as np

    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def _read_frames(path: str) -> dict:
    """``{step: positions (n, 3)}`` of a trajectory written as .npy
    shards with a ``manifest.json``; {} where there is none."""
    import numpy as np

    try:
        with open(os.path.join(path, "manifest.json")) as f:
            shards = json.load(f)["shards"]
    except (OSError, ValueError, KeyError):
        return {}
    frames = {}
    for shard in shards:
        frames.update(zip(shard["steps"],
                          np.load(os.path.join(path, shard["file"]))))
    return frames


def _checkpoint_faults(path: str, due: list, n: int, frames: dict) -> int:
    """Checkpoints on disk that are not due, do not load, have the wrong
    shape or positions other than the trajectory's frame of their step;
    plus one where the newest due checkpoint is not among those sound."""
    import numpy as np
    import orbax.checkpoint as ocp

    if not os.path.isdir(path):
        return 1
    faults, sound = 0, set()
    mgr = ocp.CheckpointManager(path)
    try:
        for step in mgr.all_steps():
            try:
                got = dict(mgr.restore(step,
                                       args=ocp.args.StandardRestore()))
            except Exception:  # noqa: BLE001 — any unreadable snapshot
                faults += 1
                continue
            shapes = tuple(np.shape(got.get(k)) for k in
                           ("positions", "velocities", "masses"))
            ok = (step in due and shapes == ((n, 3), (n, 3), (n,))
                  and (step not in frames or np.array_equal(
                      _bits(got["positions"]), _bits(frames[step]))))
            if ok:
                sound.add(step)
            else:
                faults += 1
    finally:
        mgr.close()
    return faults + (0 if due and due[-1] in sound else 1)


def output_gaps(config, *, first: int, steps: int, x_final,
                idx) -> dict:
    """The outputs the traffic switched on, checked after a window of
    steps ``first + 1 .. first + steps``. Each gap is a count of faults,
    held to 0:

    - ``trajectory_frames_off``: frames missing or not due (one every
      ``trajectory_every`` steps of the window);
    - ``trajectory_last_frame_diff``: values of the last frame that
      differ, bit for bit, from the final state at the sampled bodies;
    - ``checkpoint_faults``: ``_checkpoint_faults``."""
    gaps, frames = {}, {}
    if config.record_trajectories:
        every = config.trajectory_every
        frames = _read_frames(os.path.join(config.log_dir, "trajectories"))
        want = {first + every * (k + 1) for k in range(steps // every)}
        gaps["trajectory_frames_off"] = len(want ^ set(frames))
        last = frames.get(first + steps)
        gaps["trajectory_last_frame_diff"] = (
            3 * len(idx) if last is None else
            int((_bits(last[idx]) != _bits(x_final[idx])).sum()))
    if config.checkpoint_every:
        every = config.checkpoint_every
        due = [s for s in range(first + 1, first + steps + 1)
               if s % every == 0]
        gaps["checkpoint_faults"] = _checkpoint_faults(
            config.checkpoint_dir, due, len(x_final), frames)
    return gaps


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t0: float, age0: float = 0.0, peaks: dict | None = None,
             sim_overrides: dict | None = None,
             trace_dir: str = TRACE_DIR, out_dir: str = OUT_DIR) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    line's dict. ``sim_overrides`` sizes a rehearsal or switches on the
    program's own lower-precision path (tests and the control only)."""
    import jax
    import numpy as np

    from chipbench import ics
    from chipbench.fence import fence
    from chipbench.reference import window as ref
    from chipbench.trace import (WINDOW_END, WINDOW_START, DeviceTrace,
                                 load_xplane, newest_xplane)
    from gravity_tpu.config import SimulationConfig
    from gravity_tpu.simulation import Simulator
    from gravity_tpu.state import ParticleState

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    base = cell["base"]
    # The configuration names the work of its force evaluation; without
    # it no roofline can be read, so a run refuses to start.
    per_eval = registry.module("work", cfg["work"], base=base).per_eval
    sim_kw = dict(cfg["simulation"], **(sim_overrides or {}))
    n, block = sim_kw["n"], cell["block_steps"]
    steps = window_steps(seconds, block, cell["nominal_step_s"])
    if sim_kw.get("mesh_shape") is not None:
        sim_kw["mesh_shape"] = tuple(sim_kw["mesh_shape"])
    sim_kw.update(steps=steps, progress_every=block,
                  io_pipeline=traffic["io_pipeline"])
    outs = {k: traffic[k] for k in OUTPUTS if traffic.get(k)}
    first = 0
    if outs:
        logs = os.path.join(out_dir, cell["name"])
        sim_kw.update(
            record_trajectories="trajectory_every" in outs,
            trajectory_every=outs.get("trajectory_every", 1),
            checkpoint_every=outs.get("checkpoint_every", 0),
            log_dir=logs, checkpoint_dir=os.path.join(logs, "checkpoints"))
        if "checkpoint_every" in outs:
            first = -block % outs["checkpoint_every"]
    devs = jax.devices()
    used = devs[:cell["chips"]]

    # --- set-up: inputs from the seed, the simulator, one warm block ---
    with jax.default_device(devs[0]):
        pos, vel, m = ics.make(dict(cfg, simulation=sim_kw), seed, base=base)
    x0, v0, masses = (np.asarray(a) for a in (pos, vel, m))
    config = SimulationConfig(**sim_kw)
    sim = Simulator(config, state=ParticleState(pos, vel, m))
    del pos, vel, m
    start = sim.state  # run() never donates the state it starts from
    with jax.profiler.TraceAnnotation("chipbench.warmup"):
        io = _open_outputs(config, n) if outs else {}
        # The fence's reduction warms on the arrays the window ends on.
        warm = sim.run(steps=first + block, start_step=first,
                       **io)["final_state"]
        fence(warm.positions, warm.velocities)
        _close_outputs(io)
        del warm
    sim.state = start
    del start
    io = _open_outputs(config, n) if outs else {}

    # --- the window ---
    compiles = _CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        compiles.on = True
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_START):
            stats = sim.run(steps=first + steps, start_step=first, **io)
        with jax.profiler.TraceAnnotation(WINDOW_END):
            final = stats.pop("final_state")
            fence(final.positions, final.velocities)
        t_w1 = time.perf_counter()
        compiles.on = False
    finally:
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(compiles)
        _close_outputs(io)
    setup_s = age0 + (t_w0 - t0)
    window_s = t_w1 - t_w0
    device = _device_line(devs, used)
    xS, vS = np.asarray(final.positions), np.asarray(final.velocities)
    del sim, final
    gc.collect()
    evals = (steps * cfg["force_evals"]["per_step"]
             + cfg["force_evals"]["per_run"])
    # The work the per-layer metrics read, counted after the window.
    work = (per_eval(dict(cfg, simulation=sim_kw), x0, masses) if trace
            else None)

    # --- the check, after the window and with the program's state freed ---
    t_c0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.check"):
        idx = ref.sample_targets(seed, n, cell["sample_targets"])
        check = registry.module("reference", cfg["check"],
                                base=base).integrate_targets
        x_ref, v_ref = check(x0, v0, masses, idx, steps=steps,
                             **_reference_kwargs(check, sim_kw))
        gaps = ref.compare(x0[idx], v0[idx], xS[idx], vS[idx], x_ref, v_ref)
        out_gaps = {}
        if outs:
            out_gaps = output_gaps(config, first=first, steps=steps,
                                   x_final=xS, idx=idx)
            shutil.rmtree(config.log_dir, ignore_errors=True)
    check_s = time.perf_counter() - t_c0
    checks = {k: {"value": gaps[k], "limit": v}
              for k, v in cell["limits"].items()}
    checks.update({k: {"value": v, "limit": 0} for k, v in out_gaps.items()})
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    out = {"correct": bool(correct), "attempted": steps, "failed": 0}
    if not trace:
        out["metrics"] = {
            "step_s": {"value": window_s / steps, "unit": "s/step"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        out["device"] = device
    else:
        dtrace = DeviceTrace(load_xplane(newest_xplane(trace_dir)))
        facts = Facts(n=n, chips=cell["chips"], evals=evals,
                      window_s=window_s, stats=stats, peaks=peaks or {},
                      compiles=compiles.n, trace=dtrace,
                      flops=work["flops"] * evals,
                      bytes=work["bytes"] * evals)
        modules = registry.metric_modules()
        out["metrics"] = {}
        for name in registry.per_layer_for(cell["name"]):
            value = modules[name].read(facts)
            if value is not None:
                out["metrics"][name] = {"value": value,
                                        "unit": modules[name].UNIT}
        device.update(busy_s=dtrace.mean_busy_s(),
                      window_s=dtrace.window_ns / 1e9)
        out["device"] = device
        out["breakdown"] = dtrace.breakdown()
    out["window"] = {"steps": steps, "block_steps": block,
                     "seconds": window_s, "force_evals": evals,
                     "compiles": compiles.n, "sampled_bodies": len(idx),
                     "check_s": check_s,
                     "dv_worst": gaps["dv_worst"],
                     "dx_total": gaps["dx_total"],
                     "dv_median": gaps["dv_median"],
                     "worst_body": int(idx[gaps["worst_target"]])
                     if gaps["worst_target"] >= 0 else None}
    if work is not None:
        out["window"]["work"] = work_line(work, peaks or {})
    out["checks"] = checks
    return out


def _parse(argv):
    ap = argparse.ArgumentParser(prog="python3 -m chipbench",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t0: float | None = None, age0: float = 0.0) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = _parse(argv)
    cell = registry.cell(args.workload)
    _use_cpu_devices_for_reference()
    try:
        import jax

        import gravity_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"chipbench: cannot import the program: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              "device(s)", file=sys.stderr)
        return 3
    peaks = registry.peaks(devs[0].device_kind)
    _enable_cache(jax)
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t0=t0, age0=age0, peaks=peaks)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
