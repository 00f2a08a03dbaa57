"""The control of ``correct``, and the program's own readings, at a cell's size.

    python3 tests/chipbench/chip_control.py --workload <cell> \
        --seconds <s> --seeds <a> <b> ... [--ref-bf16] [--program] \
        [--program-bf16]

For each seed, one JSON line per reading, all in one process (the cell
compiles once):

- ``control_ref_bf16`` (``--ref-bf16``): the reference put in the program's place with
  its pair arithmetic in bfloat16 (the configuration's ``"check"``:
  ``reference/window.py`` or ``reference/cutoff.py``), from the
  seed's initial conditions, compared with the float64 reference the
  way a run compares the program;
- ``program`` (``--program``): one run of the cell as the driver makes
  it, the reading the limit's lower end is set from;
- ``program_bf16`` (``--program-bf16``): the program's own bfloat16
  path (``dtype: bfloat16``) run as the cell.

The benchmark's own runs never run this; ``test_chipbench_run.py``
runs its control at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness, registry  # noqa: E402


def control_ref_bf16(cell: dict, seed: int, seconds: float,
                     sim_overrides: dict | None = None) -> dict:
    """The control's gaps for ``seed`` (the configuration's reference
    vs the same reference in bfloat16), from the benchmark's initial
    conditions."""
    import jax
    import numpy as np

    from chipbench import ics
    from chipbench.reference import window as ref

    cfg = cell["config_data"]
    sim = dict(cfg["simulation"], **(sim_overrides or {}))
    n = sim["n"]
    steps = harness.window_steps(seconds, cell["block_steps"],
                                 cell["nominal_step_s"])
    with jax.default_device(jax.devices()[0]):
        x0, v0, m = (np.asarray(a) for a in ics.make(
            dict(cfg, simulation=sim), seed, base=cell["base"]))
    idx = ref.sample_targets(seed, n, cell["sample_targets"])
    check = registry.module("reference", cfg["check"],
                            base=cell["base"]).integrate_targets
    kw = dict(steps=steps, **harness._reference_kwargs(check, sim))
    x_r, v_r = check(x0, v0, m, idx, **kw)
    x_c, v_c = check(x0, v0, m, idx, pair_dtype="bfloat16", **kw)
    return ref.compare(x0[idx], v0[idx], x_c, v_c, x_r, v_r)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ref-bf16", action="store_true")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--program-bf16", action="store_true")
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    harness._use_cpu_devices_for_reference()
    import jax

    devs = jax.devices()
    # The reference's control needs the chip only for the initial
    # conditions, which the harness makes on one device.
    chips = cell["chips"] if args.program or args.program_bf16 else 1
    if devs[0].platform != "tpu" or len(devs) < chips:
        print("chip_control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    peaks = registry.peaks(devs[0].device_kind)
    harness._enable_cache(jax)
    runs = [("control_ref_bf16", None)] if args.ref_bf16 else []
    if args.program:
        runs.append(("program", {}))
    if args.program_bf16:
        runs.append(("program_bf16", {"dtype": "bfloat16"}))
    for seed in args.seeds:
        for kind, over in runs:
            t0 = time.perf_counter()
            if over is None:
                line = {"gaps": control_ref_bf16(cell, seed, args.seconds)}
            else:
                out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                       trace=False, t0=t0, peaks=peaks,
                                       sim_overrides=over)
                line = {k: out[k] for k in ("correct", "metrics", "window",
                                            "checks", "device")}
            print(json.dumps(dict(kind=kind, seed=seed, **line,
                                  wall_s=time.perf_counter() - t0)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
