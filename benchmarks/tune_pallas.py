"""Sweep Pallas direct-sum kernels: tile sizes AND formulations.

Three kernels implement the same force contract with different hardware
mappings: the VPU elementwise kernel over rectangular (tile_i, tile_j)
tiles (`ops/pallas_forces.py` ``pallas_accelerations_vs``, ``vpu``), its
symmetric all-pairs form that evaluates each unordered pair of square
(T, T) tiles once (``pallas_pairwise_accelerations``, ``sym``), and the
MXU matmul formulation (`ops/pallas_forces_mxu.py`, Gram-trick r^2 +
matmul accumulation, fp32 or bf16-with-fp32-accumulation, ``mxu``). This
sweep finds the tiles maximizing pair-interactions/s (directed pairs,
N(N-1) per evaluation, whatever the kernel evaluates) for each
formulation at a given N, reports every point's roofline position
(achieved TFLOP/s and MFU against the detected chip's peak), and prints
the A/B verdicts against ``vpu``. Run on a real TPU chip; results feed
the tile defaults in the kernel modules.

Usage:
    python benchmarks/tune_pallas.py [N] [--eps EPS]
        [--formulation vpu,sym,mxu] [--precision fp32|bf16|both]

--formulation takes a comma-separated subset (default: all three).

--precision applies to the mxu formulation only (the VPU kernel runs in
the state dtype); "both" A/Bs fp32 against bf16-input/fp32-accum.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gravity_tpu.utils.platform import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import jax  # noqa: E402

from gravity_tpu.utils.timing import roofline, sync, warm_sync  # noqa: E402

TILES_I = (256, 512, 1024, 2048)
TILES_J = (512, 1024, 2048)
# The symmetric form's square tile.
TILES_SYM = (512, 1024, 2048)
FORMULATIONS = ("vpu", "sym", "mxu")


def _time_kernel(f, pos, n, iters=5):
    out = f(pos)
    # warm_sync: compiles the fence's per-shape reduction outside the
    # timed region (a cold fence would bill its compile below).
    warm_sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(pos)
    sync(out)
    dt = (time.perf_counter() - t0) / iters
    return dt, n * (n - 1) / dt


def main(argv) -> int:
    n = int(argv[0]) if argv and not argv[0].startswith("-") else 65536
    eps = 1.0e9
    if "--eps" in argv:
        eps = float(argv[argv.index("--eps") + 1])
    which = set(FORMULATIONS)
    if "--formulation" in argv:
        which = set(argv[argv.index("--formulation") + 1].split(","))
    prec = "fp32"
    if "--precision" in argv:
        prec = argv[argv.index("--precision") + 1]
    if not which or which - set(FORMULATIONS):
        print(f"unknown --formulation {sorted(which)!r}", file=sys.stderr)
        return 2
    if prec not in ("fp32", "bf16", "both"):
        print(f"unknown --precision {prec!r}", file=sys.stderr)
        return 2

    from gravity_tpu.models import create_plummer
    from gravity_tpu.ops.pallas_forces import (
        pallas_accelerations_vs,
        pallas_pairwise_accelerations,
    )
    from gravity_tpu.ops.pallas_forces_mxu import (
        pallas_pairwise_accelerations_mxu,
    )

    device = jax.devices()[0]
    platform = device.platform
    interpret = platform != "tpu"
    state = create_plummer(jax.random.PRNGKey(0), n)
    pos, masses = state.positions, state.masses
    print(f"platform={platform} device_kind={device.device_kind} "
          f"n={n} eps={eps:g}")

    # variant label -> (formulation key, dtype for the peak lookup,
    # (tile_i, tile_j) points, fn)
    rect_tiles = [(ti, tj) for ti in TILES_I for tj in TILES_J]
    variants = {}
    if "vpu" in which:
        variants["vpu/fp32"] = ("vpu", "float32", rect_tiles,
                                lambda ti, tj: (
            lambda p: pallas_accelerations_vs(
                p, p, masses, eps=eps, tile_i=ti, tile_j=tj,
                interpret=interpret,
            )
        ))
    if "sym" in which:
        # Square tiles: pallas_pairwise_accelerations takes a tile as
        # wide as the wider of tile_i and tile_j.
        variants["sym/fp32"] = ("vpu", "float32",
                                [(t, t) for t in TILES_SYM],
                                lambda ti, tj: (
            lambda p: pallas_pairwise_accelerations(
                p, masses, eps=eps, tile_i=ti, tile_j=tj,
                interpret=interpret,
            )
        ))
    if "mxu" in which:
        for p_ in (("fp32", "bf16") if prec == "both" else (prec,)):
            dtype = "bfloat16" if p_ == "bf16" else "float32"
            variants[f"mxu/{p_}"] = ("mxu", dtype, rect_tiles,
                                     lambda ti, tj, p_=p_: (
                lambda p: pallas_pairwise_accelerations_mxu(
                    p, masses, eps=eps, tile_i=ti, tile_j=tj,
                    precision=p_, interpret=interpret,
                )
            ))

    best = {}  # label -> (pairs/s, tile_i, tile_j, mfu)
    for label, (form, dtype, tiles, make) in variants.items():
        print(f"\n== {label} ==")
        for tile_i, tile_j in tiles:
            try:
                dt, pairs = _time_kernel(make(tile_i, tile_j), pos, n)
            except Exception as e:
                print(f"tile_i={tile_i:5d} tile_j={tile_j:5d}: "
                      f"FAILED {type(e).__name__}")
                continue
            roof = roofline(
                pairs, formulation=form,
                device_kind=device.device_kind, dtype=dtype,
            )
            mfu = roof["mfu"]
            mfu_s = f"mfu={mfu:6.2%}" if mfu is not None else "mfu=n/a"
            print(
                f"tile_i={tile_i:5d} tile_j={tile_j:5d}: "
                f"{dt * 1e3:8.2f} ms  {pairs:.3e} pairs/s  "
                f"{roof['achieved_tflops']:7.2f} TFLOP/s  {mfu_s}"
            )
            prev = best.get(label)
            if prev is None or pairs > prev[0]:
                best[label] = (pairs, tile_i, tile_j, mfu)

    if best:
        print("\n== best per formulation ==")
        for label, (pairs, ti, tj, mfu) in best.items():
            mfu_s = f"mfu={mfu:.2%}" if mfu is not None else "mfu=n/a"
            print(f"{label:10s} tile_i={ti} tile_j={tj} "
                  f"{pairs:.3e} pairs/s  {mfu_s}")
        if "vpu/fp32" in best:
            for label, (pairs, *_rest) in best.items():
                if label != "vpu/fp32":
                    ratio = pairs / best["vpu/fp32"][0]
                    print(f"A/B {label} vs vpu/fp32: {ratio:.2f}x")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
