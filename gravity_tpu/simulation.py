"""The simulation driver — one unified runtime for every backend.

Replaces the reference's three siloed `main` loops
(`/root/reference/mpi.c:140-269`, `/root/reference/cuda.cu:120-178`,
`/root/reference/pyspark.py:104-121,152-200`) with a single orchestrator:
build ICs -> resolve force backend + sharding -> jit one multi-step
``lax.scan`` block -> run blocks, logging/recording between them. The whole
hot loop lives on-device (no per-step host round-trip — the reference's
central inefficiency: per-step D2H at `cuda.cu:159-160` and per-step
broadcast+collect at `pyspark.py:66-78`).
"""

from __future__ import annotations

import contextlib
import math
import time as _time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import SimulationConfig
from .models import create_model
from .ops.forces import accelerations_vs, pairwise_accelerations_chunked
from .ops.integrators import FORCE_EVALS_PER_STEP, init_carry, make_step_fn
from .ops import diagnostics
from .state import ParticleState
from .telemetry import tracing as _tracing
from .utils import faults as _faults
from .utils.logging import RunLogger
from .utils.timing import (
    DIRECT_SUM_BACKENDS,
    StepTimer,
    sync,
    throughput,
)
from .utils.trajectory import TrajectoryWriter

_DTYPES = {
    "float32": jnp.float32,
    "float64": jnp.float64,
    "bfloat16": jnp.bfloat16,
}


def resolve_dtype(name: str):
    if name not in _DTYPES:
        raise ValueError(f"unknown dtype {name!r}; choose from {sorted(_DTYPES)}")
    return _DTYPES[name]


# Direct-sum/fast-solver crossover for backend='auto' (docs/scaling.md).
# TPU: the gather-bound tree was MEASURED on a v5e never to catch the
# Pallas direct sum up to 1M (time ratio 80x at 65k, 6.6x at 1M,
# halving per doubling of N -> tree crossover ~8M;
# benchmarks/crossover.py, 2026-07-31). The dense-grid FMM removes the
# gathers, but the 2026-08-01 live-chip measurement (run_baselines
# 1m-fmm: 16.71 s/eval at 1M disk vs the Pallas direct sum's 5.97
# s/eval, same chip/model family) shows the direct sum still wins at
# 1M by 2.8x — the ~512k cost model undercounted how hard the MXU
# drives the dense N^2 relative to the FMM's many small shifted-slice
# passes. Scaling the two measured points (direct O(N^2), fmm ~O(N))
# puts the intersection at ~2.9M; the default snaps UP to the 4M
# ladder point so the exact direct sum keeps the boundary region
# (1M/2M BASELINE configs route direct, measured-fastest AND exact).
# A live three-way benchmarks/crossover.py sweep still overrides this
# via CROSSOVER_TPU.json (measurement beats model). CPU: measured with
# the native FFI kernel, the tree wins from ~32k (BASELINE.md).
FMM_CROSSOVER_TPU = 4_194_304
TREE_CROSSOVER_TPU = 8_388_608
TREE_CROSSOVER_CPU = 32_768
_CROSSOVER_FILE = "CROSSOVER_TPU.json"
_crossover_cache: dict = {}


def crossover_file_path() -> str:
    """Where the measured TPU crossover sweep lives — ONE resolver
    shared by the reader (:func:`_measured_fast_crossover`) and the
    writer (``benchmarks/crossover.py``), so the sweep can never write
    where the router does not read (review finding).

    ``GRAVITY_TPU_CROSSOVER_FILE`` overrides the dev-layout default
    (the repo root two levels up breaks for installed site-packages
    layouts)."""
    import os as _os

    return _os.environ.get("GRAVITY_TPU_CROSSOVER_FILE") or _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        _CROSSOVER_FILE,
    )


def _measured_fast_crossover(on_tpu: bool) -> tuple[int, str]:
    """(N, backend): above N, backend='auto' routes to this fast solver.

    On TPU, prefers the chip measurement benchmarks/crossover.py writes
    to CROSSOVER_TPU.json (repo root) over the cost-model default — the
    router's contract is "provably picks the measured-fastest backend",
    so a measurement always wins over a model. The file's
    ``winning_backend`` is honored too: a sweep where only the TREE
    beat direct must not route to fmm in the very regime fmm was
    measured to lose (review finding)."""
    if not on_tpu:
        return TREE_CROSSOVER_CPU, "tree"
    import json as _json
    import os as _os

    # The cache is keyed on (path, mtime) so a sweep written
    # mid-process — e.g. by a crossover sweep — takes effect on
    # the next Simulator without a restart (advisor finding).
    path = crossover_file_path()
    try:
        mtime = _os.path.getmtime(path)
    except OSError:
        mtime = None
    key = (path, mtime)
    if _crossover_cache.get("key") != key:
        value, backend = FMM_CROSSOVER_TPU, "fmm"
        if mtime is not None:
            try:
                with open(path) as f:
                    data = _json.load(f)
                value = int(data["fast_crossover"])
                if data.get("winning_backend") in ("tree", "fmm", "sfmm"):
                    backend = data["winning_backend"]
            except (OSError, KeyError, ValueError, TypeError):
                pass
        _crossover_cache["key"] = key
        _crossover_cache["tpu"] = (value, backend)
    return _crossover_cache["tpu"]


# Forcing O(N^2) here means >=2.7e11 pairs/step — minutes/step on CPU,
# multiple seconds/step on one chip. Probably a mistake; warn.
DIRECT_SUM_WARN_N = 524_288
# Above this N the collision-merge pass detects candidates with the O(N)
# cell grid instead of the exact O(N^2) scan (ops/encounters.py); below
# it the brute pass is already sub-second and exact at any radius.
MERGE_GRID_THRESHOLD = 32_768
# Above this N a tree/p3m run prices its --metrics-energy sample with the
# O(N log N) tree potential instead of the dense O(N^2) pair scan (which
# would cost more than the force step it monitors; ops/tree.py).
ENERGY_TREE_THRESHOLD = 16_384
# Above this N the in-program conservation ledger's energy term switches
# from the chunked dense pair scan (exact, O(N^2) per block) to the
# jittable scaled tree/fmm potential sums — same crossover logic as the
# consume-time sample above, but BOTH paths stay async-dispatchable
# device programs (docs/observability.md "Numerics"). Defined in
# ops/diagnostics so the serve engine's vmapped twin shares the bound
# without importing this module.
from .ops.diagnostics import LEDGER_DENSE_MAX  # noqa: E402
# Multirate fast kicks with K * N pair entries at or under this budget
# use the exact dense (K, N) rectangular kernel; above it the
# shifted-slice backends serve the kicks with occupancy-scaled target
# caps (make_local_kernel).
DENSE_KICK_BUDGET = 1 << 25


def _resolve_direct(config: SimulationConfig, on_tpu: bool) -> str:
    """Scale-aware choice among the EXACT direct-sum backends."""
    if config.nlist_rcut > 0.0:
        # Declared truncated physics (the nlist family): the exact
        # reference is the rcut-MASKED direct sum, which only the jnp
        # forms implement — pallas/cpp compute full gravity and would
        # silently change the physics.
        return "dense" if config.n <= 4096 else "chunked"
    if on_tpu and config.n >= 1024:
        return "pallas"
    if config.n <= 4096:
        return "dense"
    # CPU platform at mid scale: the multithreaded C++ XLA FFI kernel
    # runs ~2x faster than the chunked jnp path (measured at 8k, r2).
    # The availability probe builds the library on first use (one
    # cached g++ compile, seconds — the CPU analog of a first Mosaic
    # kernel compile) and is a cheap dlopen afterwards.
    if (
        jax.devices()[0].platform == "cpu"
        and config.dtype in ("float32", "float64")
    ):
        from .ops.ffi_forces import ffi_forces_available

        if ffi_forces_available():
            return "cpp"
    return "chunked"


def _resolve_backend(config: SimulationConfig, on_tpu=None) -> str:
    """Resolve 'auto'/'direct' to a concrete backend. ``on_tpu``
    overrides platform detection (tests)."""
    backend = config.force_backend
    if backend == "auto" and config.periodic_box > 0.0:
        if config.nlist_rcut > 0.0:
            # Declared truncated physics in a periodic box: nlist is
            # the only periodic member of the truncated family (pm
            # computes FULL gravity — routing there would silently
            # discard the declared rcut).
            return "nlist"
        return "pm"  # the only periodic-capable FULL-gravity solver
    if backend not in ("auto", "direct"):
        if (
            config.nlist_rcut > 0.0
            and backend not in ("nlist", "dense", "chunked")
        ):
            # Only the nlist kernel and the jnp direct forms honor the
            # rcut mask; every other backend computes FULL gravity.
            # The explicit choice wins, but silently is how physics
            # bugs ship.
            import warnings

            warnings.warn(
                f"nlist_rcut={config.nlist_rcut:g} declares truncated "
                f"short-range physics, but force_backend={backend!r} "
                "computes FULL gravity and ignores it (only nlist/"
                "dense/chunked honor the rcut mask)",
                stacklevel=2,
            )
        _warn_n = DIRECT_SUM_WARN_N
        if (
            backend in ("pallas", "pallas-mxu")
            and jax.devices()[0].platform == "tpu"
        ):
            # On the chip the Pallas kernels ARE the measured fast path
            # up to the tree crossover (docs/scaling.md) — only warn
            # where the tree would actually win.
            _warn_n = TREE_CROSSOVER_TPU
        if (
            backend in DIRECT_SUM_BACKENDS
            and config.n >= _warn_n
            # A ring shard streams sources and can never assemble the
            # full set a global tree build needs, so there is no faster
            # alternative to suggest — don't nag the merger preset.
            and config.sharding != "ring"
            # Declared-truncated physics (nlist_rcut > 0): the masked
            # direct sum is the exact reference of that family; the
            # full-gravity fast solvers this warning would suggest
            # compute different physics.
            and config.nlist_rcut <= 0.0
        ):
            import warnings

            warnings.warn(
                f"force_backend={backend!r} is a direct O(N^2) sum; at "
                f"n={config.n} that is {config.n * (config.n - 1) // 2:.3g} "
                "pair interactions per force evaluation. The 'tree' (or "
                "periodic 'pm'/'p3m') solver is orders of magnitude faster "
                "at this scale; pass force_backend='auto' to select it.",
                stacklevel=2,
            )
        return backend
    if on_tpu is None:
        on_tpu = jax.devices()[0].platform == "tpu"
    if backend == "direct":
        # Exactness guarantee without hardware knowledge: never routes
        # to an approximate solver regardless of scale.
        return _resolve_direct(config, on_tpu)
    if config.nlist_rcut > 0.0:
        # Declared truncated physics: the static route stays in the
        # exact-truncated family (the rcut-masked direct sum); the
        # autotuner — not this crossover model — promotes the nlist
        # kernel when it measures faster (full-gravity fast solvers
        # are a different physics and must never be auto-routed here).
        return _resolve_direct(config, on_tpu)
    # auto: above the measured crossover a fast solver wins over any
    # direct sum — unless the ring strategy is requested (see above).
    # On TPU the chip measurements put that crossover HIGH: the Pallas
    # direct sum beat the tree 6.6x and the dense-grid FMM 2.8x at 1M
    # (docs/scaling.md; 2026-07-31 / 2026-08-01 live), so every
    # BASELINE config through the 2M merger routes direct, and the FMM
    # (the gather-free winner among the fast solvers) takes over at
    # the measured-extrapolated ~3M boundary; sharded runs use the
    # slab-decomposed make_sharded_fmm_accel, multirate fast kicks the
    # rectangular fmm_accelerations_vs. A recorded chip sweep
    # (CROSSOVER_TPU.json) overrides both the threshold and the winner.
    # (This static route is the probe-free fallback; a Simulator-owned
    # 'auto' consults the measurement-driven autotune cache FIRST via
    # _resolve_backend_for_run — gravity_tpu/autotune.py, docs/
    # scaling.md "Autotuned routing" — so at runtime the crossover
    # model below only decides when autotuning is off or no candidate
    # could be probed.)
    crossover, fast_backend = _measured_fast_crossover(on_tpu)
    if config.n >= crossover and config.sharding != "ring":
        if fast_backend == "sfmm" and config.sharding != "none":
            # Auto on a mesh conservatively degrades to the slab-
            # sharded dense fmm (a measured, chip-validated path) even
            # when a sweep crowned sfmm: the chunk-sharded sparse form
            # exists (make_sharded_sfmm_accel, explicit
            # force_backend='sfmm') but has no chip numbers yet.
            return "fmm"
        return fast_backend
    return _resolve_direct(config, on_tpu)


def _resolve_backend_for_run(config: SimulationConfig, state) -> tuple:
    """(backend, autotune facts) for a Simulator about to run.

    Plain ``force_backend='auto'`` consults the measurement-driven
    tuning cache (gravity_tpu/autotune.py): instant on a cache hit,
    a micro-probe of the eligible candidates on a miss — so 'auto'
    means "measured fastest", not "modeled fastest". Everything else
    (explicit backends, 'direct', periodic runs — pm is the only
    periodic solver — or ``autotune=False``) keeps the static
    resolution, reported as ``cache='off'``. The autotuner must never
    kill a run: any resolution failure falls back to the static route
    with a warning.
    """
    backend = _resolve_backend(config)
    off = {"cache": "off", "probe_ms": 0.0}
    if (
        config.force_backend != "auto"
        or not config.autotune
        or config.periodic_box > 0.0
    ):
        return backend, off
    from .autotune import resolve_backend_measured

    try:
        d = resolve_backend_measured(
            config, state, static_fallback=backend
        )
    except Exception as e:  # noqa: BLE001 — routing is an optimization;
        # a broken probe must degrade to the static router, not abort.
        import warnings

        warnings.warn(
            f"backend autotune failed ({type(e).__name__}: {e}); "
            f"falling back to the static route {backend!r}",
            stacklevel=2,
        )
        return backend, off
    return d.backend, {
        "cache": d.cache, "probe_ms": round(d.probe_ms, 3)
    }


def _resolve_depth_and_warn(config: SimulationConfig, positions, where,
                            n=None) -> int:
    """Tree-family depth resolution + the HBM cell-structure audit —
    the ONE place both happen (every tree/fmm solver-build path calls
    this, so the audit cannot silently drop off one of them)."""
    from .ops.tree import (
        recommended_depth,
        recommended_depth_data,
        warn_if_cell_memory_heavy,
    )

    depth = config.tree_depth or (
        recommended_depth_data(positions, config.tree_leaf_cap)
        if positions is not None
        else recommended_depth(config.n, config.tree_leaf_cap)
    )
    warn_if_cell_memory_heavy(
        n if n is not None else config.n, depth, config.tree_leaf_cap,
        where,
        dtype_bytes={"float64": 8, "bfloat16": 2}.get(config.dtype, 4),
    )
    return depth


def _occupancy_t_cap(cap: int, k_targets: int, n: int, positions,
                     side: int, where: str) -> int:
    """Static target-slot cap for a ~K-target rectangular kick on a
    side^3 cell grid.

    Mean-occupancy sizing (4x clustering headroom) is the fallback; when
    concrete initial positions are available the K fastest particles are
    modeled as landing density-proportionally — the expected target
    count in a cell scales with that cell's occupancy, so the densest
    cell needs ~K * max_count / N slots (2x headroom on top). Mean-based
    sizing silently degrades exactly the close-encounter kicks to the
    monopole fallback in clustered runs (advisor finding, round 4);
    when even the full cap cannot hold the modeled densest-cell target
    load, warn instead of silently overflowing.
    """
    mean_based = max(4, -(-4 * cap * k_targets // max(1, n)))
    if positions is None or not getattr(
        positions, "is_fully_addressable", True
    ):
        # Multi-host mesh: the global array cannot be fetched to this
        # host (same guard as ops.tree.recommended_depth_data); fall
        # back to the mean-based estimate rather than crash.
        return min(cap, mean_based)
    pos = np.asarray(positions, dtype=np.float64)
    lo = pos.min(axis=0)
    span = float(np.max(pos.max(axis=0) - lo)) or 1.0
    u = np.clip(
        ((pos - lo[None, :]) / span * side).astype(np.int64), 0, side - 1
    )
    ids = (u[:, 0] * side + u[:, 1]) * side + u[:, 2]
    max_count = int(np.bincount(ids, minlength=side**3).max())
    density_based = -(-2 * k_targets * max_count // max(1, n))
    if density_based > cap:
        import warnings

        warnings.warn(
            f"{where}: the densest cell holds {max_count} of {n} bodies; "
            f"~{density_based} fast-rung target slots would be needed "
            f"but the static cap is {cap} — a fraction of fast kicks "
            "will take the softened monopole fallback. Raise the cell "
            "cap or deepen the grid.",
            stacklevel=3,
        )
    return min(cap, max(mean_based, density_based))


def _resolve_nlist_config(config: SimulationConfig, positions):
    """The ONE (side, cap) resolution for the nlist backend — shared by
    the local-kernel and unsharded builders (and reported in
    ``Simulator.nlist_sizing``), so audits and bench lines always
    describe the cell list the run actually used. Explicit config knobs
    win; otherwise the sizing is fit to concrete initial positions
    (pallas_nlist.resolve_nlist_sizing). Callers with neither (serve
    bucket kernels size blind at admission) must set --nlist-side."""
    if config.nlist_rcut <= 0.0:
        raise ValueError(
            "force_backend='nlist' needs nlist_rcut > 0 (--nlist-rcut): "
            "the cell-list kernel computes forces TRUNCATED at rcut — "
            "declared short-range physics, not an approximation of "
            "full gravity"
        )
    from .ops.pallas_nlist import DEFAULT_CAP, resolve_nlist_sizing

    side, cap = config.nlist_side, config.nlist_cap
    if side and cap:
        return side, cap
    if positions is None or not getattr(
        positions, "is_fully_addressable", True
    ):
        if not side:
            raise ValueError(
                "nlist sizing needs concrete initial positions or an "
                "explicit --nlist-side (serve jobs must set it: no "
                "state exists at admission)"
            )
        return side, cap or DEFAULT_CAP
    return resolve_nlist_sizing(
        np.asarray(positions), config.nlist_rcut, cap=cap, side=side,
        box=config.periodic_box,
    )


def _resolve_halo_nlist_config(
    config: SimulationConfig, positions, devices: int,
):
    """:func:`_resolve_nlist_config` for the domain-decomposed form:
    the as-run side must split into whole cell planes per device, so
    auto-sizing goes through parallel/halo.resolve_halo_sizing and an
    explicit ``--nlist-side`` is validated rather than silently
    rounded (the solo and halo forms must agree on what was run — the
    --debug-check audit replays exactly this sizing)."""
    if config.nlist_rcut <= 0.0:
        raise ValueError(
            "force_backend='nlist' needs nlist_rcut > 0 (--nlist-rcut): "
            "the cell-list kernel computes forces TRUNCATED at rcut — "
            "declared short-range physics, not an approximation of "
            "full gravity"
        )
    from .ops.pallas_nlist import DEFAULT_CAP
    from .parallel.halo import resolve_halo_sizing

    side, cap = config.nlist_side, config.nlist_cap
    if side and side % devices:
        raise ValueError(
            f"halo nlist needs --nlist-side divisible by the mesh axis "
            f"size; got side={side}, devices={devices} (round it, or "
            "set nlist_mesh='allgather')"
        )
    if side and cap:
        return side, cap
    if positions is None or not getattr(
        positions, "is_fully_addressable", True
    ):
        if not side:
            raise ValueError(
                "nlist sizing needs concrete initial positions or an "
                "explicit --nlist-side (serve jobs must set it: no "
                "state exists at admission)"
            )
        return side, cap or DEFAULT_CAP
    return resolve_halo_sizing(
        np.asarray(positions), config.nlist_rcut, cap=cap,
        devices=devices, side=side, box=config.periodic_box,
    )


def _p3m_halo_side(config: SimulationConfig, mesh) -> int:
    """The device-divisible near-field cell side for the halo-sharded
    p3m form, or 0 when this mesh cannot host it (multi-axis, or the
    axis no longer fits whole cell planes). Rounding the solo
    ``binning_side`` DOWN to a multiple of D keeps the 27-neighborhood
    covering rcut (fewer, larger cells) — rounding up would shrink
    cells below the truncation radius and silently drop near pairs."""
    from .ops.p3m import binning_side

    if len(mesh.axis_names) != 1:
        return 0
    devices = mesh.shape[mesh.axis_names[0]]
    side = binning_side(
        config.pm_grid, config.p3m_sigma_cells, config.p3m_rcut_sigmas
    )
    side = (side // devices) * devices
    return side if side >= max(devices, 2) else 0


def _make_nlist_kernel(config: SimulationConfig, positions=None,
                       k_targets=None):
    """LocalKernel for the cutoff-radius cell-list backend. The Pallas
    tile engine on TPU (dense-vjp-wrapped: pallas_call has no autodiff
    rule), the jnp reference engine elsewhere; the K-target hint sizes
    the static target cap to the expected fast-rung occupancy exactly
    like the other shifted-slice backends."""
    import warnings

    from .ops.pallas_nlist import check_nlist_sizing, make_nlist_local_kernel

    side, cap = _resolve_nlist_config(config, positions)
    note = check_nlist_sizing(config.n, side, cap)
    if note:
        warnings.warn(note, stacklevel=3)
    t_cap = 0
    if k_targets is not None:
        t_cap = _occupancy_t_cap(
            cap, k_targets, config.n, positions, side, "nlist kernel"
        )
    return make_nlist_local_kernel(
        rcut=config.nlist_rcut, side=side, cap=cap, t_cap=t_cap,
        g=config.g, cutoff=config.cutoff, eps=config.eps,
        box=config.periodic_box,
    )


def make_local_kernel(config: SimulationConfig, backend: str,
                      positions=None, k_targets=None):
    """LocalKernel (pos_targets, pos_sources, m_sources) -> acc for the
    resolved backend.

    The fast solvers (tree/pm/p3m) fit this signature too: each chip
    rebuilds the tree/mesh from the full gathered source set (replicated
    work, cheap — O(N) with small constants) and evaluates only its target
    slice (the dominant cost, perfectly sharded). They require the
    ``allgather`` strategy: a ring over source shards cannot build a
    global tree or mesh.

    ``positions`` (optional, concrete) lets the tree depth auto-tuner
    count occupied leaves instead of assuming uniform 3D occupancy —
    pass the initial state whenever it exists (disks/halos are lower-
    dimensional and the count-only estimate under-resolves them badly).

    ``k_targets`` (optional) declares that callers will pass ~K targets
    per call (the multirate fast rung). The shifted-slice backends'
    rectangular cost scales with their static target-slot cap, NOT with
    K, so without the hint a K-target kick would cost a full force
    evaluation; with it, fmm sizes t_cap to the expected per-cell
    target occupancy (4x headroom for clustering), and a K small enough
    for the dense (K, N) kick budget short-circuits to the exact dense
    kernel (review finding).
    """
    # Injection point for the supervisor's degrade ladder: a platform
    # that cannot build this kernel surfaces here as BackendUnavailable
    # (utils/faults.py makes that failure exercisable on CPU).
    _faults.check_backend(backend)
    common = dict(g=config.g, cutoff=config.cutoff, eps=config.eps)
    if backend in ("dense", "chunked"):
        # "chunked" differs only in the unsharded full-N path below; as a
        # local kernel (slice vs sources) dense jnp is the right shape.
        # Declared truncated physics (nlist_rcut > 0) masks the pair set
        # at rcut — the exact reference of the nlist family.
        if config.nlist_rcut > 0.0:
            common = dict(common, rcut=config.nlist_rcut)
        return partial(accelerations_vs, **common)
    if backend == "nlist":
        return _make_nlist_kernel(config, positions, k_targets)
    if backend == "pallas":
        from .ops.pallas_forces import make_pallas_local_kernel

        interpret = jax.devices()[0].platform != "tpu"
        return make_pallas_local_kernel(interpret=interpret, **common)
    if backend == "pallas-mxu":
        # The MXU matmul-formulation direct sum (Gram-trick r^2 + matmul
        # force accumulation; precision follows the state dtype — bf16
        # states run bf16 operands with fp32 accumulation). Explicit
        # opt-in until the chip A/B (benchmarks/tune_pallas.py) crowns
        # it: 'direct'/'auto' keep routing to the measured VPU kernel.
        from .ops.pallas_forces_mxu import make_pallas_mxu_local_kernel

        interpret = jax.devices()[0].platform != "tpu"
        return make_pallas_mxu_local_kernel(interpret=interpret, **common)
    if backend == "cpp":
        if jax.devices()[0].platform != "cpu":
            raise ValueError(
                "force_backend='cpp' (native XLA FFI kernel) is a CPU-"
                "platform backend; on TPU use 'pallas'"
            )
        if config.dtype not in ("float32", "float64"):
            raise ValueError(
                f"force_backend='cpp' supports float32/float64, not "
                f"{config.dtype!r}"
            )
        from .ops.ffi_forces import ffi_forces_available, make_ffi_local_kernel

        if not ffi_forces_available():
            raise _faults.BackendUnavailable(
                "cpp", "g++ toolchain or jax.ffi headers missing"
            )
        return make_ffi_local_kernel(**common)
    if backend == "tree":
        from .ops.tree import tree_accelerations_vs

        depth = _resolve_depth_and_warn(config, positions, "tree kernel")
        return partial(
            tree_accelerations_vs, depth=depth,
            leaf_cap=config.tree_leaf_cap, ws=config.tree_ws,
            far=config.tree_far, chunk=config.fast_chunk,
            near_mode=config.tree_near, **common,
        )
    if backend in ("fmm", "sfmm"):
        # The rectangular (targets-vs-sources) multirate kicks use the
        # dense-grid form for both fmm modes: the fast-kick target set
        # is small and re-binned per call, where the sparse layout's
        # compaction prologue would dominate its own savings.
        from .ops.fmm import fmm_accelerations_vs

        if k_targets is not None and k_targets * config.n <= DENSE_KICK_BUDGET:
            # Tiny target sets: the exact dense (K, N) kick is cheaper
            # than any grid pass and has zero approximation error.
            return partial(accelerations_vs, **common)
        depth = _resolve_depth_and_warn(config, positions, "fmm kernel")
        t_cap = 0
        if k_targets is not None:
            t_cap = _occupancy_t_cap(
                config.tree_leaf_cap, k_targets, config.n, positions,
                1 << depth, "fmm kernel",
            )
        return partial(
            fmm_accelerations_vs, depth=depth,
            leaf_cap=config.tree_leaf_cap, ws=config.tree_ws,
            t_cap=t_cap, **common,
        )
    if backend == "pm":
        if config.periodic_box > 0.0:
            from .ops.periodic import pm_periodic_accelerations_vs

            return partial(
                pm_periodic_accelerations_vs, box=config.periodic_box,
                grid=config.pm_grid, g=config.g, eps=config.eps,
                assignment=config.pm_assignment,
            )
        from .ops.pm import pm_accelerations_vs

        return partial(
            pm_accelerations_vs, grid=config.pm_grid, g=config.g,
            eps=config.eps, assignment=config.pm_assignment,
        )
    if backend == "p3m":
        import warnings

        from .ops.p3m import check_p3m_sizing, p3m_accelerations_vs

        note = check_p3m_sizing(
            config.n, config.pm_grid, config.p3m_sigma_cells,
            config.p3m_rcut_sigmas, config.p3m_cap, positions=positions,
        )
        if note:
            warnings.warn(note, stacklevel=2)
        t_cap = 0
        if k_targets is not None:
            # Slice-mode rectangular cost scales with the target cap;
            # size it to the expected K-target cell occupancy instead
            # of the full cap.
            from .ops.p3m import binning_side

            t_cap = _occupancy_t_cap(
                config.p3m_cap, k_targets, config.n, positions,
                binning_side(
                    config.pm_grid, config.p3m_sigma_cells,
                    config.p3m_rcut_sigmas,
                ),
                "p3m kernel",
            )
        return partial(
            p3m_accelerations_vs, grid=config.pm_grid,
            sigma_cells=config.p3m_sigma_cells,
            rcut_sigmas=config.p3m_rcut_sigmas,
            cap=config.p3m_cap, chunk=config.fast_chunk,
            short_mode=config.p3m_short, t_cap=t_cap, **common,
        )
    raise ValueError(f"unknown force backend {backend!r}")


_DONATION_PROBE: Optional[bool] = None


def donation_supported() -> bool:
    """Whether ``donate_argnums`` actually reuses buffers in place on
    this platform, probed once per process: jit a trivial donating op
    and check the output aliases the donated input. XLA's donation
    support varies by backend AND version (current jaxlib aliases on
    CPU too), so a hardcoded platform list goes stale. The BENCH line
    reports this as ``donated`` so an A/B reader knows whether in-place
    buffer reuse was in effect."""
    global _DONATION_PROBE
    if _DONATION_PROBE is None:
        try:
            probe = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
            x = jnp.zeros((8,), jnp.float32)
            ptr = x.unsafe_buffer_pointer()
            _DONATION_PROBE = bool(
                probe(x).unsafe_buffer_pointer() == ptr
            )
        except Exception:  # noqa: BLE001 — exotic backends without
            # unsafe_buffer_pointer: fall back to the classic list
            _DONATION_PROBE = jax.devices()[0].platform in ("tpu", "gpu")
    return _DONATION_PROBE


class SimulationDiverged(RuntimeError):
    """The state went NaN/Inf mid-run (integration blow-up, bad dt, or a
    kernel fault). Carries the last finite step for post-mortems."""

    def __init__(self, step: int):
        super().__init__(
            f"non-finite particle state detected after step {step} "
            "(divergence watchdog; rerun with a smaller dt or softer eps, "
            "or disable with nan_check=False)"
        )
        self.step = step


class AccuracyBreach(RuntimeError):
    """The accuracy sentinel measured a force error past the declared
    ``--error-budget`` (docs/observability.md "Numerics"). The state is
    FINITE — unlike divergence there is nothing to roll back; the
    supervisor heals by re-sizing the solver (leaf caps) or rerouting
    down the exact-physics ladder and continues from the last consumed
    block. Standalone runs exit 2 with a structured error, exactly like
    the other watchdogs."""

    def __init__(self, step: int, backend: str, p90_rel_err: float,
                 budget: float):
        super().__init__(
            f"accuracy breach at step {step}: backend {backend!r} "
            f"sentinel p90 relative force error {p90_rel_err:.3e} "
            f"exceeds the error budget {budget:.3e} (raise the budget, "
            "re-size the solver, or run with --auto-recover to heal)"
        )
        self.step = step
        self.backend = backend
        self.p90_rel_err = p90_rel_err
        self.budget = budget


class SimulationPreempted(KeyboardInterrupt):
    """SIGTERM (scheduler preemption) converted to an exception.

    Subclasses :class:`KeyboardInterrupt` deliberately: the run loops'
    interrupt handler already checkpoints-and-reraises on
    KeyboardInterrupt, and preemption must take the exact same
    checkpoint-and-exit path (ISSUE 2 satellite). Callers that care
    (CLI, supervisor) catch this subclass first and exit with the
    dedicated resumable code (supervisor.EXIT_PREEMPTED) so schedulers
    can distinguish "requeue me" from failure.
    """


@contextlib.contextmanager
def preemption_guard():
    """Convert SIGTERM into :class:`SimulationPreempted` for the enclosed
    block, restoring the previous handler on exit.

    No-op outside the main thread (CPython only delivers signals there)
    and wherever the interpreter refuses handler installation — the run
    then keeps its default SIGTERM behavior instead of crashing.
    """
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def _handler(signum, frame):
        raise SimulationPreempted("SIGTERM received (preemption)")

    try:
        prev = signal.signal(signal.SIGTERM, _handler)
    except ValueError:  # embedded interpreters without signal support
        yield
        return
    try:
        yield
    finally:
        signal.signal(signal.SIGTERM, prev)


def make_initial_state(config: SimulationConfig) -> ParticleState:
    """THE derivation of a run's initial model state from its config
    (key, model, dtype, box) — shared by :class:`Simulator` and the
    CLI's supervised path, so a supervised run can size its trajectory
    writer before any (possibly failing) kernel build without ever
    disagreeing with what the legs integrate."""
    return create_model(
        config.model, jax.random.PRNGKey(config.seed), config.n,
        resolve_dtype(config.dtype), periodic_box=config.periodic_box,
    )


class Simulator:
    """Orchestrates a full run for a :class:`SimulationConfig`."""

    def __init__(self, config: SimulationConfig,
                 state: Optional[ParticleState] = None):
        self.config = config
        self.dtype = resolve_dtype(config.dtype)
        # Which fmm layout the build resolved to (False until an
        # fmm/sfmm accel builder runs; benchmarks introspect this).
        self.fmm_sparse = False
        # As-run nlist cell-list sizing (side, cap, evaluated pair
        # tiles/eval) — set by the nlist accel builder; the bench
        # harness reads it for the honest roofline.
        self.nlist_sizing = None
        # Share of the evaluated pair tiles that serve both of their
        # bodies (the symmetric Pallas all-pairs kernel; 0 elsewhere).
        self.pair_symmetric_share = 0.0

        # State before backend resolution: plain 'auto' routes through
        # the measurement-driven autotuner (gravity_tpu/autotune.py),
        # which probes candidates against THIS initial state and keys
        # its cache on the state's occupancy signature.
        if state is None:
            state = make_initial_state(config)
        else:
            state = state.astype(self.dtype)
        self.n_real = state.n
        self.backend, self.autotune = _resolve_backend_for_run(
            config, state
        )
        if "@" in self.backend:
            # Composite mesh-strategy candidate ("nlist@halo" /
            # "nlist@allgather"): the measured winner carries its mesh
            # strategy — pin it into the run's config so the accel
            # build below takes exactly the probed program.
            import dataclasses as _dc

            self.backend, _strategy = self.backend.split("@", 1)
            config = _dc.replace(config, nlist_mesh=_strategy)
            self.config = config

        # Sharding setup: pad N to a multiple of the mesh size, shard the
        # particle axis (the reference pads nothing; zero-mass padding is
        # exact — see ParticleState.pad_to).
        self.mesh = None
        if config.sharding != "none":
            if config.sharding == "ring" and self.backend in (
                "tree", "fmm", "sfmm", "pm", "p3m", "nlist"
            ):
                raise ValueError(
                    f"force backend {self.backend!r} needs the full source "
                    "set per chip to build its tree/mesh; use "
                    "sharding='allgather'"
                )
            from .parallel import make_particle_mesh, shard_state

            self.mesh = make_particle_mesh(config.mesh_shape)
            p = self.mesh.size
            n_pad = math.ceil(state.n / p) * p
            state, _ = state.pad_to(n_pad)
            state = shard_state(state, self.mesh)

        self.state = state
        self._build_fns()

    def _nlist_mesh_strategy(self) -> str:
        """Resolved mesh strategy for the cell-list family (the nlist
        backend, and p3m's erfc near field): 'halo' (slab domain
        decomposition, parallel/halo.py) or 'allgather'.
        'auto' takes halo whenever the slab form applies — a
        single-axis mesh with >= 2 devices — so mesh nlist runs get
        O(surface) comms by default; 'halo' insists (error when
        inapplicable); 'allgather' pins the gather-the-world path."""
        mode = self.config.nlist_mesh
        if mode not in ("auto", "halo", "allgather"):
            raise ValueError(
                f"nlist_mesh must be 'auto', 'halo' or 'allgather'; "
                f"got {mode!r}"
            )
        applicable = (
            self.mesh is not None
            and len(self.mesh.axis_names) == 1
            and self.mesh.shape[self.mesh.axis_names[0]] >= 2
        )
        if mode == "halo":
            if not applicable:
                raise ValueError(
                    "nlist_mesh='halo' needs a single-axis mesh with "
                    ">= 2 devices (the slab decomposition runs over "
                    "one mesh axis)"
                )
            return "halo"
        if mode == "allgather" or not applicable:
            return "allgather"
        return "halo"

    def _build_fns(self) -> None:
        """Build the (positions, masses) -> acc function and the jitted
        block runner.

        Masses reach the hot loop as a TRACED operand (read off the
        scanned ParticleState), not as a baked closure constant — so runs
        whose masses change mid-flight (particle merging) keep hitting
        the same compiled block instead of retracing.
        """
        config = self.config
        if config.periodic_box > 0.0 and self.backend not in (
            "pm", "nlist"
        ):
            raise ValueError(
                "periodic_box > 0 needs a periodic-capable solver — "
                "'pm' (full gravity, FFT) or 'nlist' (truncated "
                f"short-range, minimum-image cell list); got "
                f"{self.backend!r} — tree/p3m/direct backends are "
                "isolated-BC"
            )
        # Optional per-block precompute hook (aux built inside the jitted
        # block but OUTSIDE its scan): set by backends whose accel has a
        # step-invariant expensive prefix. p3m uses it for the Ewald
        # kernel transform — XLA does not hoist the in-graph build out of
        # while bodies (measured on the compiled HLO), so without this a
        # 500-step block would pay 3 extra grid-sized FFTs per step.
        self._accel_setup = None
        self._accel2_aux = None
        mesh_sparse = self.mesh is not None and (
            self.backend == "sfmm"
            or (self.backend == "fmm" and config.fmm_mode == "sparse")
        )
        if (
            self.mesh is not None
            and not mesh_sparse
            and self.backend == "fmm"
            and config.fmm_mode == "auto"
            and getattr(
                self.state.positions, "is_fully_addressable", True
            )
        ):
            # Occupancy routing fires for EVERY fast-solver selection,
            # mesh included (VERDICT r5 item 4): a clustered state whose
            # occupied cells are <5% of the dense grid routes to the
            # chunk-sharded sparse layout — the same threshold as the
            # single-host auto decision below, on the same
            # dryrun-validated make_sharded_sfmm_accel path. Multi-host
            # meshes (positions not addressable from this host) keep
            # the dense slab route: the occupancy count needs the
            # global array.
            from .ops.sfmm import sfmm_auto_decision

            mesh_sparse, mesh_sizing = sfmm_auto_decision(
                self.state.positions, config.tree_leaf_cap
            )
        else:
            mesh_sizing = None
        if mesh_sparse:
            # Chunk-sharded sparse FMM: replicated compaction/eval, the
            # dominant per-cell chunk stages split 1/P per device, one
            # all_gather per channel.
            from .ops.sfmm import make_sharded_sfmm_accel, resolve_sfmm_sizing

            if mesh_sizing is not None and not config.tree_depth:
                # The auto decision above already paid the host-side
                # O(N) binning; reuse its sizing instead of re-running
                # the identical pass (mirrors the single-host dedupe).
                depth_s, cap_s, k_cells, _ = mesh_sizing
            else:
                depth_s, cap_s, k_cells = resolve_sfmm_sizing(
                    self.state.positions, config.tree_depth,
                    config.tree_leaf_cap,
                )
            self.fmm_sparse = True
            self._accel2 = make_sharded_sfmm_accel(
                self.mesh, depth=depth_s, leaf_cap=cap_s,
                k_cells=k_cells, ws=config.tree_ws, g=config.g,
                cutoff=config.cutoff, eps=config.eps,
            )
            # Audits read the EFFECTIVE (device-divisible) k AND the
            # as-run chunk width the solver runs with, not the nominal
            # sizing: replaying k_eff through the default 8192-chunk
            # rounding would re-inflate it (e.g. 20000 -> 24576) and
            # audit a solver with more rank capacity than the one that
            # produced the trajectory (review findings).
            self.sfmm_sizing = (
                depth_s, cap_s, self._accel2.k_eff,
                self._accel2.k_chunk_eff,
            )
        elif self.mesh is not None and self.backend == "fmm":
            # Sharded fmm splits the dominant slab passes over the mesh
            # (replicated build, one (cells, cap, 3) all_gather) — work
            # scales 1/P without the per-device target re-binning the
            # rectangular fmm_accelerations_vs path would need.
            from .ops.fmm import make_sharded_fmm_accel

            depth = _resolve_depth_and_warn(
                config, self.state.positions, "sharded fmm",
                n=self.state.n,
            )
            self._accel2 = make_sharded_fmm_accel(
                self.mesh, depth=depth, leaf_cap=config.tree_leaf_cap,
                ws=config.tree_ws, g=config.g, cutoff=config.cutoff,
                eps=config.eps,
            )
        elif self.mesh is not None and self.backend == "nlist" and (
            self._nlist_mesh_strategy() == "halo"
        ):
            # Domain-decomposed slabs (parallel/halo.py): O(surface)
            # halo comms + O(N/D) local tile work instead of gathering
            # the world. The as-run sizing is the D-rounded halo form —
            # audits (--debug-check) and the bench roofline read it,
            # and re-deriving from the EVOLVED final state (or from the
            # solo rounding) would audit a different cell list than the
            # one that ran.
            from .ops.pallas_nlist import evaluated_pairs_per_eval
            from .parallel.halo import (
                make_halo_nlist_accel, resolve_mig_cap,
            )

            axis = self.mesh.axis_names[0]
            devices = self.mesh.shape[axis]
            side, cap = _resolve_halo_nlist_config(
                config, self.state.positions, devices
            )
            self.nlist_sizing = (
                side, cap, evaluated_pairs_per_eval(side, cap)
            )
            mig_cap = config.nlist_mig_cap
            if not mig_cap and getattr(
                self.state.positions, "is_fully_addressable", True
            ):
                mig_cap = resolve_mig_cap(
                    np.asarray(self.state.positions), side, devices,
                    box=config.periodic_box,
                )
            self._accel2 = make_halo_nlist_accel(
                self.mesh, side=side, cap=cap, rcut=config.nlist_rcut,
                g=config.g, cutoff=config.cutoff, eps=config.eps,
                box=config.periodic_box, mig_cap=mig_cap,
            )
        elif self.mesh is not None and self.backend == "p3m" and (
            self._nlist_mesh_strategy() == "halo"
        ) and (
            _p3m_halo_side(config, self.mesh) > 0
            or config.nlist_mesh == "halo"
        ):
            # Sharded P3M with the halo near field: the PM far pass
            # stays the replicated-build allgather form (a global FFT
            # has no slab locality to exploit), while the erfc near
            # field — the pairwise cost that dominates at scale — runs
            # the domain-decomposed cell exchange with kind='ewald'.
            import math as _math
            import warnings as _warnings

            from .ops.p3m import _mesh_accelerations, check_p3m_sizing
            from .ops.pm import bounding_cube
            from .parallel import make_sharded_accel2
            from .parallel.halo import make_halo_nlist_accel

            side = _p3m_halo_side(config, self.mesh)
            if not side:
                raise ValueError(
                    "nlist_mesh='halo' on sharded p3m needs the near-"
                    "field cell grid to fit >= 1 whole cell plane per "
                    "device; this mesh cannot host the slab form — "
                    "set nlist_mesh='allgather' (or shrink the mesh)"
                )
            note = check_p3m_sizing(
                config.n, config.pm_grid, config.p3m_sigma_cells,
                config.p3m_rcut_sigmas, config.p3m_cap,
                positions=self.state.positions,
            )
            if note:
                _warnings.warn(note, stacklevel=2)
            grid = config.pm_grid
            sc = config.p3m_sigma_cells

            def _far_local(targets, sources, m_src):
                origin, span = bounding_cube(sources)
                return _mesh_accelerations(
                    targets, sources, m_src, origin, span,
                    grid=grid, g=config.g, sigma_cells=sc,
                )

            far = make_sharded_accel2(
                self.mesh, strategy="allgather",
                local_kernel=_far_local, g=config.g,
                cutoff=config.cutoff, eps=config.eps,
            )
            near = make_halo_nlist_accel(
                self.mesh, side=side, cap=config.p3m_cap,
                g=config.g, cutoff=config.cutoff, eps=config.eps,
                kind="ewald",
                ewald_scales=(
                    (grid - 1) / (_math.sqrt(2.0) * sc),
                    config.p3m_rcut_sigmas * sc / (grid - 1),
                ),
            )
            self._accel2 = lambda p, m: far(p, m) + near(p, m)
        elif self.mesh is not None:
            from .parallel import make_sharded_accel2

            if self.backend == "nlist":
                # The as-run sizing for the sharded form too: audits
                # (--debug-check) and the bench roofline read it, and
                # re-deriving from the EVOLVED final state would audit
                # a different cell list than the one that ran.
                from .ops.pallas_nlist import evaluated_pairs_per_eval

                side, cap = _resolve_nlist_config(
                    config, self.state.positions
                )
                self.nlist_sizing = (
                    side, cap, evaluated_pairs_per_eval(side, cap)
                )
            self._accel2 = make_sharded_accel2(
                self.mesh,
                strategy=config.sharding,
                local_kernel=make_local_kernel(
                    config, self.backend, positions=self.state.positions
                ),
                g=config.g,
                cutoff=config.cutoff,
                eps=config.eps,
            )
        else:
            self._accel2 = self._unsharded_accel2()

        # Self-gravity accel BEFORE the external-field wrap: the
        # accuracy sentinel's exact oracle is the direct sum of
        # self-gravity only, so the probe must audit this form.
        self._self_accel2 = self._accel2
        self._ext_phi = None
        ext = None
        if config.external:
            from .ops.external import parse_external

            ext = parse_external(config.external)
            # Parsed once here; energy() reuses the potential twin.
            self._ext_phi = parse_external(config.external, kind="potential")
            self_gravity = self._accel2
            # O(N) elementwise add: composes with every backend and
            # shards trivially with the positions.
            self._accel2 = lambda pos, m: self_gravity(pos, m) + ext(pos)
            if self._accel2_aux is not None:
                aux_gravity = self._accel2_aux
                self._accel2_aux = (
                    lambda pos, m, aux: aux_gravity(pos, m, aux) + ext(pos)
                )

        self._local_vs_kernel = None
        self._rect_accel = None
        self._fast_fast_kernel = None
        if config.integrator == "multirate":
            if config.multirate_k < 0 or config.multirate_sub < 1:
                raise ValueError(
                    "multirate_k must be >= 0 (0 = auto) and "
                    "multirate_sub >= 1; got "
                    f"k={config.multirate_k}, sub={config.multirate_sub}"
                )
            if not (2 <= config.multirate_rungs <= 6):
                # 6 rungs = 32 unrolled micro-steps; beyond that the
                # trace blows up and the capacities hit the floor anyway.
                raise ValueError(
                    "multirate_rungs must be in [2, 6]; got "
                    f"{config.multirate_rungs}"
                )
            # Every backend (incl. fmm since its rectangular
            # fmm_accelerations_vs form landed) provides the (K, N)
            # LocalKernel the fast kicks need. The K hint lets the
            # shifted-slice backends size their static target caps to
            # the actual fast-rung occupancy instead of paying a
            # full-evaluation near-field pass per sub-kick.
            k_mr, _ = self._multirate_plan()
            base_kernel = make_local_kernel(
                config, self.backend, positions=self.state.positions,
                k_targets=k_mr,
            )
            if self.mesh is not None:
                # Sharded fast rung: replicated K-target rectangular
                # kick against sharded slow sources (psum-reduced), plus
                # a dense replicated fast-fast kernel; the external
                # field adds elementwise on the replicated targets.
                from .parallel import make_sharded_rect_accel

                rect = make_sharded_rect_accel(self.mesh, base_kernel)
                if ext is not None:
                    self._rect_accel = (
                        lambda ti, sj, m: rect(ti, sj, m) + ext(ti)
                    )
                else:
                    self._rect_accel = rect
                self._fast_fast_kernel = partial(
                    accelerations_vs, g=config.g, cutoff=config.cutoff,
                    eps=config.eps,
                )
            elif ext is not None:
                self._local_vs_kernel = (
                    lambda ti, sj, m: base_kernel(ti, sj, m) + ext(ti)
                )
            else:
                self._local_vs_kernel = base_kernel

        # Convenience one-arg wrapper (carry seeding, run_adaptive, the
        # bench harness): reads the CURRENT self.state's masses.
        self.accel_fn = lambda pos: self._accel2(pos, self.state.masses)
        # Performance observatory (docs/observability.md
        # "Performance"): the block fn is the solo stack's compile
        # site — every distinct (n_steps, record) signature is AOT
        # lowered+compiled once through the instrumented wrapper, its
        # XLA cost/memory analysis and compile seconds captured into
        # the perf ledger with the pair-model flop expectation, and
        # executed through the captured executable.
        from .telemetry import perf as _perf

        _tiles = (
            self.nlist_sizing[2]
            if self.backend == "nlist" and self.nlist_sizing is not None
            else None
        )
        _perf_kw = dict(
            site="solo_block",
            key=_perf.logical_key(
                "solo", backend=self.backend, n=self.state.n,
                dtype=config.dtype, integrator=config.integrator,
                sharding=(
                    config.sharding if self.mesh is not None else None
                ),
            ),
            backend=self.backend,
            n=self.state.n,
            analytic=_perf.analytic_flops(
                self.backend, self.state.n,
                force_evals=FORCE_EVALS_PER_STEP.get(
                    config.integrator, 1
                ),
                evaluated_pairs=_tiles,
            ),
        )
        self._run_block = _perf.instrument_jit(
            jax.jit(
                self._block_fn,
                static_argnames=("n_steps", "record", "record_every"),
            ),
            **_perf_kw,
        )
        # Donated twin for the pipelined driver (docs/scaling.md "Host
        # pipeline & donation"): the (state, acc) carry is donated so
        # XLA reuses its HBM in place across blocks. Legal only in the
        # pipelined loop, which consumes the previous block through the
        # non-aliased snapshot below — the serial loop reads its block
        # inputs after the call (emergency saves) and must not donate.
        self._run_block_donated = _perf.instrument_jit(
            jax.jit(
                self._block_fn,
                static_argnames=("n_steps", "record", "record_every"),
                donate_argnums=(0, 1),
            ),
            **dict(_perf_kw, meta={"donated": True}),
        )
        # Pipeline companions, dispatched on a block's outputs BEFORE
        # the next block donates them: the watchdog's finiteness verdict
        # (a scalar — fetching it is the block's completion fence) and a
        # non-aliased deep copy the host consumers (checkpoint saves,
        # energy metrics, interrupt handlers) can read while the next
        # block overwrites the donated original.
        self._finite_fn = jax.jit(
            lambda st: jnp.all(jnp.isfinite(st.positions))
            & jnp.all(jnp.isfinite(st.velocities))
        )
        self._snapshot_fn = jax.jit(
            lambda st: jax.tree_util.tree_map(jnp.copy, st)
        )
        self._build_observatory()

    def _build_observatory(self) -> None:
        """The numerics observatory's jitted companions
        (docs/observability.md "Numerics"): the conservation-ledger
        device function + host converter and the accuracy-sentinel
        probe. Both are pure functions of the state the run loop
        dispatches asynchronously right after each block — the
        ``_finite_fn`` pattern — so neither can re-serialize the host
        pipeline the way the PR-4 consume-time ``--metrics-energy``
        sample did."""
        import warnings

        config = self.config
        self._ledger_on = bool(config.ledger or config.metrics_energy)
        if config.metrics_energy and not config.ledger:
            warnings.warn(
                "--metrics-energy is a deprecated alias for the "
                "in-program conservation ledger (--ledger): the "
                "per-block energy sample is now an async device "
                "companion of the block instead of a consume-time "
                "dispatch (docs/observability.md \"Numerics\")",
                DeprecationWarning, stacklevel=3,
            )
        budget = float(config.error_budget or 0.0)
        sent_every = int(config.sentinel_every or 0)
        if budget > 0.0 and sent_every <= 0:
            # A declared budget with no cadence means "watch every
            # block": an un-probed budget cannot breach.
            sent_every = 1
        self._ledger_fn = None
        self._ledger_convert = None
        self._sentinel_fn = None
        self._sentinel_every = sent_every
        if not self._ledger_on and sent_every <= 0:
            return
        truncated = config.nlist_rcut > 0.0 and self.backend in (
            "nlist", "dense", "chunked"
        )
        rcut = config.nlist_rcut if truncated else 0.0

        if self._ledger_on:
            from .ops.diagnostics import (
                ledger_host,
                ledger_vec,
                pe_hat_dense,
            )

            n = self.state.n
            tiny = jnp.finfo(self.dtype).tiny
            if config.periodic_box > 0.0 and not truncated:
                # Full periodic gravity: the conserved energy is the
                # mesh potential the solver actually integrates.
                from .ops.periodic import _potential_core

                def pe_dev(pos, m):
                    m_mean = jnp.mean(m)
                    mw = m / jnp.maximum(m_mean, tiny)
                    return _potential_core(
                        pos, mw, (0.0, 0.0, 0.0), config.periodic_box,
                        grid=config.pm_grid, g=config.g,
                        eps=config.eps,
                        assignment=config.pm_assignment,
                    ), m_mean

                pe_kind = "pm"
            elif truncated or n <= LEDGER_DENSE_MAX:
                # Exact chunked pair scan (with the truncated family's
                # shifted rcut kernel + minimum image when periodic).
                def pe_dev(pos, m):
                    return pe_hat_dense(
                        pos, m, cutoff=config.cutoff, eps=config.eps,
                        rcut=rcut, box=config.periodic_box,
                    ), jnp.maximum(jnp.max(m), tiny)

                pe_kind = "dense"
            elif jax.devices()[0].platform == "tpu":
                # Large-n fast-solver runs price the energy term with
                # the same gather-free fmm potential the consume-time
                # sample used — but its jitted scaled core, so the
                # dispatch stays async.
                from .ops.fmm import _clamp_slab, _fmm_pe_scaled

                depth = self._ledger_tree_depth()
                slab = _clamp_slab(4, depth, config.tree_leaf_cap)

                def pe_dev(pos, m):
                    return _fmm_pe_scaled(
                        pos, m, depth=depth,
                        leaf_cap=config.tree_leaf_cap,
                        ws=config.tree_ws, g=config.g,
                        cutoff=config.cutoff, eps=config.eps,
                        slab=slab,
                    )

                pe_kind = "fmm"
            else:
                from .ops.tree import _tree_pe_scaled

                depth = self._ledger_tree_depth()

                def pe_dev(pos, m):
                    return _tree_pe_scaled(
                        pos, m, depth=depth,
                        leaf_cap=config.tree_leaf_cap,
                        chunk=config.fast_chunk, ws=config.tree_ws,
                        cutoff=config.cutoff, eps=config.eps,
                        quad=True,
                    )

                pe_kind = "tree"

            ext_phi = self._ext_phi

            def ledger_device(st: ParticleState) -> dict:
                pe, scale = pe_dev(st.positions, st.masses)
                out = {
                    "vec": ledger_vec(
                        st.positions, st.velocities, st.masses
                    ),
                    "pe": pe,
                    "pe_scale": scale,
                }
                if ext_phi is not None:
                    # --external runs conserve KE + PE_self + PE_ext:
                    # the replaced --metrics-energy path included the
                    # field's potential (self.energy()), so must the
                    # ledger. Normalized masses keep the device sum in
                    # fp32 range; ledger_host rescales by m_scale.
                    m_scale = jnp.maximum(
                        jnp.max(st.masses), tiny
                    )
                    out["ext"] = jnp.sum(
                        (st.masses / m_scale) * ext_phi(st.positions)
                    )
                return out

            self._ledger_fn = jax.jit(ledger_device)
            self._ledger_convert = lambda dev: ledger_host(
                dev["vec"], dev.get("pe"), dev.get("pe_scale"),
                g=config.g, pe_kind=pe_kind, ext=dev.get("ext"),
            )

        if sent_every > 0:
            if config.periodic_box > 0.0 and not truncated:
                warnings.warn(
                    "accuracy sentinel disabled: full periodic gravity "
                    "has no exact direct-sum oracle (the minimum-image "
                    "reference only covers the rcut-truncated nlist "
                    "family)",
                    stacklevel=3,
                )
                self._sentinel_every = 0
            else:
                from .utils.profiling import (
                    full_set_probe_kernel,
                    make_force_error_probe,
                    sentinel_indices,
                )

                idx = sentinel_indices(
                    self.state.n, config.sentinel_k, config.seed
                )
                # The probe audits the run's OWN compiled accel (the
                # sharded/fast-solver form included) against the exact
                # oracle on K fixed targets — one extra force
                # evaluation per probe, amortized by the cadence.
                self._sentinel_fn = jax.jit(make_force_error_probe(
                    full_set_probe_kernel(self._self_accel2, idx),
                    idx=idx, g=config.g, cutoff=config.cutoff,
                    eps=config.eps, rcut=rcut,
                    box=config.periodic_box if truncated else 0.0,
                ))

    def _ledger_tree_depth(self) -> int:
        """Depth for the ledger's large-n tree/fmm potential term —
        the same resolution rule as the consume-time energy sample
        (one host pass, cached per Simulator)."""
        depth = getattr(self, "_energy_tree_depth", None)
        if depth is None:
            from .ops.tree import recommended_depth_data

            depth = self.config.tree_depth or recommended_depth_data(
                self.state.positions, self.config.tree_leaf_cap
            )
            self._energy_tree_depth = depth
        return depth

    def _unsharded_accel2(self):
        """(positions, masses) -> accelerations for the resolved backend."""
        _faults.check_backend(self.backend)
        config = self.config
        n = self.state.n
        common = dict(g=config.g, cutoff=config.cutoff, eps=config.eps)
        if (
            self.backend in ("dense", "chunked")
            and config.nlist_rcut > 0.0
        ):
            # Declared truncated physics: the rcut-masked direct sum is
            # the exact reference of the nlist family (docs/scaling.md
            # "Cell-list near field").
            common = dict(common, rcut=config.nlist_rcut)
        if self.backend == "dense":
            return lambda pos, m: accelerations_vs(pos, pos, m, **common)
        if self.backend == "chunked":
            chunk = min(config.chunk, n)
            while n % chunk:
                chunk //= 2
            chunk = max(chunk, 1)
            return lambda pos, m: pairwise_accelerations_chunked(
                pos, m, chunk=chunk, **common
            )
        if self.backend == "nlist":
            from .ops.pallas_nlist import evaluated_pairs_per_eval

            side, cap = _resolve_nlist_config(
                config, self.state.positions
            )
            import warnings

            from .ops.pallas_nlist import (
                check_nlist_sizing,
                nlist_accelerations_vs,
            )

            note = check_nlist_sizing(n, side, cap)
            if note:
                warnings.warn(note, stacklevel=2)
            # The as-run sizing + evaluated-tile count, for the bench
            # harness's honest roofline (the headline rate is
            # dense-equivalent; MFU is computed on tiles actually run).
            self.nlist_sizing = (
                side, cap, evaluated_pairs_per_eval(side, cap)
            )
            return lambda pos, m: nlist_accelerations_vs(
                pos, pos, m, rcut=config.nlist_rcut, side=side, cap=cap,
                box=config.periodic_box, _self=True, **common,
            )
        if self.backend == "pallas":
            from .ops.pallas_forces import (
                make_pallas_pairwise_kernel,
                pair_symmetric_share,
            )

            self.pair_symmetric_share = pair_symmetric_share(n)
            return make_pallas_pairwise_kernel(
                interpret=jax.devices()[0].platform != "tpu", **common
            )
        if self.backend in ("pallas-mxu", "cpp"):
            kernel = make_local_kernel(config, self.backend)
            return lambda pos, m: kernel(pos, pos, m)
        if self.backend == "tree":
            from .ops.tree import tree_accelerations

            depth = _resolve_depth_and_warn(
                config, self.state.positions, "tree backend", n=n
            )
            return lambda pos, m: tree_accelerations(
                pos, m, depth=depth, leaf_cap=config.tree_leaf_cap,
                ws=config.tree_ws, far=config.tree_far,
                chunk=config.fast_chunk, near_mode=config.tree_near,
                **common,
            )
        if self.backend in ("fmm", "sfmm"):
            from .ops.sfmm import sfmm_auto_decision

            # Mode resolution (eager, from the initial state): sparse
            # when explicitly asked, or — in auto — by the shared
            # occupancy decision (sfmm_auto_decision; same rule the
            # mesh build applies).
            sizing = None
            sparse = self.backend == "sfmm" or config.fmm_mode == "sparse"
            if self.backend == "fmm" and config.fmm_mode == "auto":
                sparse, sizing = sfmm_auto_decision(
                    self.state.positions, config.tree_leaf_cap
                )
            if sparse:
                from .ops.sfmm import resolve_sfmm_sizing, sfmm_accelerations

                if sizing is not None and not config.tree_depth:
                    depth_s, cap_s, k_cells, _ = sizing
                else:
                    depth_s, cap_s, k_cells = resolve_sfmm_sizing(
                        self.state.positions, config.tree_depth,
                        config.tree_leaf_cap,
                    )
                self.fmm_sparse = True
                # The as-run sizing, for audits (cli --debug-check,
                # post-run occupancy): an audit must measure THIS
                # solver — the EFFECTIVE chunk-rounded k it runs with,
                # not a re-size from the evolved final state or the
                # nominal pre-rounding k (review findings).
                from .ops.sfmm import DEFAULT_K_CHUNK, effective_k_cells

                self.sfmm_sizing = (
                    depth_s, cap_s, effective_k_cells(k_cells),
                    DEFAULT_K_CHUNK,
                )
                return lambda pos, m: sfmm_accelerations(
                    pos, m, depth=depth_s, leaf_cap=cap_s,
                    k_cells=k_cells, ws=config.tree_ws, **common,
                )
            from .ops.fmm import fmm_accelerations

            self.fmm_sparse = False
            depth = _resolve_depth_and_warn(
                config, self.state.positions, "fmm backend", n=n
            )
            return lambda pos, m: fmm_accelerations(
                pos, m, depth=depth, leaf_cap=config.tree_leaf_cap,
                ws=config.tree_ws, **common,
            )
        if self.backend == "pm":
            if config.periodic_box > 0.0:
                from .ops.periodic import pm_periodic_accelerations

                return lambda pos, m: pm_periodic_accelerations(
                    pos, m, box=config.periodic_box, grid=config.pm_grid,
                    g=config.g, eps=config.eps,
                    assignment=config.pm_assignment,
                )
            from .ops.pm import pm_accelerations

            return lambda pos, m: pm_accelerations(
                pos, m, grid=config.pm_grid, g=config.g, eps=config.eps,
                assignment=config.pm_assignment,
            )
        if self.backend == "p3m":
            import warnings

            from .ops.p3m import check_p3m_sizing, p3m_accelerations

            note = check_p3m_sizing(
                n, config.pm_grid, config.p3m_sigma_cells,
                config.p3m_rcut_sigmas, config.p3m_cap,
                positions=self.state.positions,
            )
            if note:
                warnings.warn(note, stacklevel=2)
            from .ops.p3m import _force_kernel_hat, p3m_accelerations_vs

            self._accel_setup = lambda dtype: _force_kernel_hat(
                2 * config.pm_grid, config.p3m_sigma_cells, dtype
            )
            self._accel2_aux = lambda pos, m, khat: p3m_accelerations_vs(
                pos, pos, m, grid=config.pm_grid,
                sigma_cells=config.p3m_sigma_cells,
                rcut_sigmas=config.p3m_rcut_sigmas,
                cap=config.p3m_cap, chunk=config.fast_chunk, khat=khat,
                short_mode=config.p3m_short, **common,
            )
            return lambda pos, m: p3m_accelerations(
                pos, m, grid=config.pm_grid,
                sigma_cells=config.p3m_sigma_cells,
                rcut_sigmas=config.p3m_rcut_sigmas,
                cap=config.p3m_cap, chunk=config.fast_chunk,
                short_mode=config.p3m_short, **common,
            )
        raise ValueError(self.backend)

    def _multirate_plan(self):
        """(k, capacities | None) for the multirate configuration — ONE
        derivation of the auto-k default and the 8^(r-1) capacity ladder
        (used by the fixed-dt block and the adaptive composition), with
        the oversized-ladder guard applied in both."""
        config = self.config
        n = self.state.n
        k = min(config.multirate_k or max(1, n // 8), n)
        rungs = config.multirate_rungs
        if rungs > 2:
            capacities = tuple(
                max(1, k // (8 ** (r - 1))) for r in range(1, rungs)
            )
            if sum(capacities) > n:
                raise ValueError(
                    f"rung capacities {capacities} (from "
                    f"multirate_k={k}, rungs={rungs}) exceed "
                    f"n={n}; lower multirate_k"
                )
            return k, capacities
        return k, None

    # --- the jitted hot loop ---

    def _block_fn(self, state: ParticleState, acc, *, n_steps: int,
                  record: bool, record_every: int = 1):
        # The step fn binds masses from the TRACED state, so mass edits
        # between blocks (merging) don't invalidate the compiled block.
        masses = state.masses
        if self.config.integrator == "multirate":
            from .ops.multirate import (
                make_multirate_sharded_step_fn,
                make_multirate_step_fn,
                make_rung_ladder_sharded_step_fn,
                make_rung_ladder_step_fn,
            )

            k, capacities = self._multirate_plan()
            if capacities is not None:
                # Power-of-two ladder: rung r capacity k // 8^(r-1),
                # floored at 1 (GADGET-style geometric occupancy).
                if self.mesh is not None:
                    step = make_rung_ladder_sharded_step_fn(
                        self.mesh, self._rect_accel,
                        self._fast_fast_kernel, self._accel2,
                        self.config.dt, capacities=capacities,
                    )
                else:
                    step = make_rung_ladder_step_fn(
                        self._local_vs_kernel, self.config.dt,
                        capacities=capacities, accel_full=self._accel2,
                    )
            elif self.mesh is not None:
                step = make_multirate_sharded_step_fn(
                    self.mesh, self._rect_accel, self._fast_fast_kernel,
                    self._accel2, self.config.dt,
                    k=k, n_sub=self.config.multirate_sub,
                )
            else:
                step = make_multirate_step_fn(
                    self._local_vs_kernel, self.config.dt,
                    k=k, n_sub=self.config.multirate_sub,
                    # The once-per-step full eval goes through the
                    # backend's memory-bounded path (chunked/tree/...),
                    # not the dense rectangular kernel used for the
                    # (K, N) fast kicks.
                    accel_full=self._accel2,
                )
        else:
            if self._accel_setup is not None and self._accel2_aux is not None:
                # Step-invariant prefix hoisted out of the scan by
                # construction: built here (inside the jitted block),
                # closed over as tracers by the step body.
                aux = self._accel_setup(state.positions.dtype)
                accel = lambda pos: self._accel2_aux(pos, masses, aux)
            else:
                accel = lambda pos: self._accel2(pos, masses)
            step = make_step_fn(
                self.config.integrator, accel, self.config.dt,
            )

        def body(carry, _):
            st, a = carry
            st, a = step(st, a)
            return (st, a), None

        def wrap(st: ParticleState) -> ParticleState:
            # Periodic runs: re-wrap once per block (forces are wrap-
            # invariant, so this only protects fp precision over long
            # drifts, not correctness within a block).
            if self.config.periodic_box <= 0.0:
                return st
            box = jnp.asarray(self.config.periodic_box,
                              st.positions.dtype)
            return st.replace(positions=jnp.mod(st.positions, box))

        if not record:
            (state, acc), _ = jax.lax.scan(
                body, (state, acc), None, length=n_steps
            )
            return wrap(state), acc, None

        # Recording: emit one (N, 3) frame per `record_every` steps, so the
        # scan output (and its D2H transfer) is 1/record_every the size of
        # naively stacking every step. n_steps must divide into strides.
        assert n_steps % record_every == 0

        def stride(carry, _):
            (st, a), _ = jax.lax.scan(body, carry, None, length=record_every)
            return (st, a), st.positions

        (state, acc), traj = jax.lax.scan(
            stride, (state, acc), None, length=n_steps // record_every
        )
        return wrap(state), acc, traj

    def _make_host_pipeline(self, trajectory_writer, checkpoint_manager,
                            enabled: bool, telemetry=None,
                            trace_id: Optional[str] = None):
        """The background-writer half of the host pipeline, shared by
        the fixed-dt and adaptive drivers: returns ``(host_writer,
        trajectory_writer, submit_save)``. With ``enabled`` and any I/O
        consumer present, trajectory records and checkpoint saves route
        through one bounded-queue :class:`~gravity_tpu.utils.hostio.
        HostWriter`; otherwise ``host_writer`` is None and
        ``submit_save`` saves inline (the serial path). With a
        telemetry bundle attached, every checkpoint save emits a
        ``checkpoint`` span (timed where it RUNS — on the background
        thread under the pipeline); the profiler sees it either way."""
        host_writer = None
        if enabled and (
            trajectory_writer is not None or checkpoint_manager is not None
        ):
            from .utils.hostio import HostWriter
            from .utils.trajectory import AsyncTrajectoryWriter

            host_writer = HostWriter()
            if trajectory_writer is not None:
                trajectory_writer = AsyncTrajectoryWriter(
                    trajectory_writer, host_writer
                )

        tracer = telemetry.tracer if telemetry is not None else None

        def _save(at_step, at_state, extra=None):
            from .utils.checkpoint import save_checkpoint

            with _tracing.span("checkpoint", tracer, trace_id,
                               step=at_step):
                save_checkpoint(
                    checkpoint_manager, at_step, at_state, extra=extra
                )

        def submit_save(at_step, at_state, extra=None):
            # The background writer runs the SHA-256 payload checksum
            # and the Orbax save off the critical path.
            if host_writer is not None:
                host_writer.submit(_save, at_step, at_state, extra=extra)
            else:
                _save(at_step, at_state, extra=extra)

        return host_writer, trajectory_writer, submit_save

    def _resolve_io_pipeline(self) -> bool:
        """True when this run drives the depth-1 async host pipeline
        (docs/scaling.md "Host pipeline & donation"): dispatch block k+1,
        then consume block k's outputs while k+1 runs on device."""
        mode = self.config.io_pipeline
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"io_pipeline must be 'auto', 'on', or 'off'; got {mode!r}"
            )
        if mode == "off":
            return False
        if self.config.merge_radius > 0.0:
            # The merge pass reads AND edits the live state at block
            # boundaries — the in-flight next block would integrate the
            # pre-merge state it was dispatched from.
            if mode == "on":
                raise ValueError(
                    "io_pipeline='on' does not compose with collision "
                    "merging (merge_radius > 0): the merge pass edits "
                    "the live state at block boundaries, which the "
                    "in-flight block would ignore; use io_pipeline="
                    "'auto' (degrades to the serial loop) or 'off'"
                )
            return False
        return True

    def run(
        self,
        logger: Optional[RunLogger] = None,
        *,
        steps: Optional[int] = None,
        trajectory_writer: Optional[TrajectoryWriter] = None,
        checkpoint_manager=None,
        metrics_logger=None,
        start_step: int = 0,
        telemetry=None,
    ) -> dict:
        """Run the configured number of steps; returns a results dict.

        ``telemetry`` (a :class:`~gravity_tpu.telemetry.Telemetry`
        bundle, CLI: ``--trace``) gives the solo run the serving
        stack's span structure — per-block ``block`` spans,
        ``checkpoint`` spans, and flight-recorder dumps on divergence
        and preemption (docs/observability.md). Adaptive runs take the
        supervisor's recorder triggers only. The driver's phases are
        spans (``run``, ``run.open``, ``block.dispatch``/``wait``/
        ``consume``, ``run.drain``, ``run.close``) on the profiler's
        clock whether or not ``telemetry`` is given.

        ``config.adaptive`` runs dispatch to :meth:`run_adaptive` — the
        CLI did this already, but a Python-API caller setting
        ``adaptive=True`` and calling ``run()`` must not silently get a
        fixed-dt integration (review finding).

        SIGTERM during the run raises :class:`SimulationPreempted`
        through the same checkpoint-and-exit path as Ctrl-C, so
        preempted runs are resumable.
        """
        tracer = trace_id = None
        if telemetry is not None:
            tracer, trace_id = telemetry.tracer, _tracing.new_trace_id()
        with preemption_guard(), _tracing.span("run", tracer, trace_id):
            return self._run_impl(
                logger, steps=steps, trajectory_writer=trajectory_writer,
                checkpoint_manager=checkpoint_manager,
                metrics_logger=metrics_logger, start_step=start_step,
                telemetry=telemetry, trace_id=trace_id,
            )

    def _run_impl(
        self,
        logger: Optional[RunLogger] = None,
        *,
        steps: Optional[int] = None,
        trajectory_writer: Optional[TrajectoryWriter] = None,
        checkpoint_manager=None,
        metrics_logger=None,
        start_step: int = 0,
        telemetry=None,
        trace_id: Optional[str] = None,
    ) -> dict:
        config = self.config
        if config.adaptive:
            if steps is not None or start_step:
                raise ValueError(
                    "adaptive runs take their span from config.steps "
                    "(t_end = steps * dt); use run_adaptive(start_t=...) "
                    "to resume"
                )
            return self.run_adaptive(
                logger, trajectory_writer=trajectory_writer,
                checkpoint_manager=checkpoint_manager,
                metrics_logger=metrics_logger,
            )
        total_steps = config.steps if steps is None else steps
        # Recording only happens when there is somewhere to put the frames;
        # config.record_trajectories alone (no writer) must not make the
        # scan stack positions that would then be discarded.
        record = trajectory_writer is not None
        every = max(1, config.trajectory_every) if record else 1
        block = max(1, min(config.progress_every, total_steps))
        if config.merge_radius > 0.0:
            # Collision checks happen at block boundaries; their cadence
            # is a physics knob (merge_every), not the logging cadence.
            block = max(1, min(block, config.merge_every))
        if record:
            # Block size must be a multiple of the recording stride.
            block = max(1, block // every) * every

        # Host pipeline resolution (docs/scaling.md "Host pipeline &
        # donation"): pipelined runs dispatch block k+1 before consuming
        # block k — the watchdog verdict, metrics, trajectory D2H +
        # writes, and checkpoint saves all overlap k+1's device compute,
        # and the (state, acc) carry is donated for in-place HBM reuse.
        pipelined = self._resolve_io_pipeline()
        self.io_pipelined = pipelined
        self.donated = pipelined and donation_supported()
        tracer = telemetry.tracer if telemetry is not None else None
        host_writer, trajectory_writer, _save_cadence = (
            self._make_host_pipeline(
                trajectory_writer, checkpoint_manager, pipelined,
                telemetry=telemetry, trace_id=trace_id,
            )
        )

        self._banner(logger, total_steps, config.integrator)

        from .utils.timing import HostGapTimer, pairs_metric_name

        self._e0 = None
        # Numerics observatory (docs/observability.md "Numerics"):
        # the conservation ledger's t0 baseline, the sentinel cadence
        # counter, and the per-run aggregates the stats report.
        ledger_on = self._ledger_on and self._ledger_fn is not None
        sent_every = (
            self._sentinel_every if self._sentinel_fn is not None
            else 0
        )
        ledger0 = None
        ledger_last = None
        drift_last = None
        max_energy_drift = None
        ledger_blocks = 0
        sent_stats = {"probes": 0, "max_rel_err": None, "last": None}
        blocks_dispatched = 0
        state = self.state
        # The state at `step`, readable by emergency saves.
        last_good = state
        with _tracing.span("run.open", tracer, trace_id):
            acc = init_carry(self.accel_fn, state)
            if ledger_on:
                ledger0 = self._ledger_convert(self._ledger_fn(state))
            if pipelined:
                # Never donate the caller-visible initial state: jax
                # marks donated arrays deleted on EVERY platform,
                # Simulator accepts prebuilt states whose buffers the
                # caller still owns (and same-dtype astype aliases
                # them), and self.state must stay readable if an error
                # fires before the first consume (the supervisor's
                # transient resume reads it). The first dispatch
                # consumes a private copy instead.
                state = self._snapshot_fn(state)
        timer = StepTimer()
        timer.start()
        gap = HostGapTimer()
        block_prev = 0.0
        step = start_step
        merged_total = 0
        # Merge cadence is a physics knob independent of the logging
        # block size: blocks may be smaller (progress_every < merge_every),
        # so count steps since the last check instead of checking every
        # block boundary.
        steps_since_merge_check = 0
        # self.state/self._last_step stay current per CONSUMED block so
        # the interrupt/preemption handler below can checkpoint mid-run
        # (pipelined runs drop the unconsumed in-flight block — `resume`
        # re-integrates it).
        self._last_step = step
        run_block = self._run_block_donated if pipelined else self._run_block
        pending = None  # pipelined: dispatched block awaiting consumption
        # The open `block.consume` span: a block's consume phase runs
        # from its completion fence to the next dispatch.
        consume = contextlib.ExitStack()

        try:
          while step < total_steps or pending is not None:
            consume.close()
            if step < total_steps:
                # Injected transient device errors surface at block start
                # (utils/faults.py); the supervisor retries them with
                # exponential backoff from the last finite in-memory state.
                _faults.maybe_raise_transient(step)
                remaining = total_steps - step
                if record and remaining >= every:
                    # Whole strides only; any sub-stride tail runs
                    # unrecorded.
                    n_steps = min(block, (remaining // every) * every)
                    do_record = True
                else:
                    n_steps = min(block, remaining)
                    do_record = False
                gap.dispatched()
                dispatch = _tracing.span(
                    "block.dispatch", tracer, trace_id, step=step,
                    steps=n_steps,
                )
                if pipelined:
                    # JAX async dispatch: these return futures; block
                    # k+1 runs on device while the host consumes k.
                    # Companions on the outputs (watchdog verdict +
                    # non-aliased snapshot) are dispatched NOW, before
                    # the next iteration donates new_state.
                    with dispatch:
                        new_state, acc, traj = run_block(
                            state, acc, n_steps=n_steps,
                            record=do_record,
                            record_every=every if do_record else 1,
                        )
                        finite = (
                            self._finite_fn(new_state)
                            if config.nan_check else None
                        )
                        snap = self._snapshot_fn(new_state)
                        # Observatory companions ride the same async
                        # dispatch as the finiteness verdict: their
                        # values are fetched at consume time through
                        # the block's own fence, so the ledger/sentinel
                        # can never re-serialize the pipeline (the
                        # --metrics-energy fix).
                        led = (
                            self._ledger_fn(new_state) if ledger_on
                            else None
                        )
                        sent = (
                            self._sentinel_fn(
                                new_state.positions, new_state.masses
                            )
                            if sent_every
                            and blocks_dispatched % sent_every == 0
                            else None
                        )
                    blocks_dispatched += 1
                    state = new_state
                    step += n_steps
                    blk, pending = pending, (
                        step - n_steps, n_steps, snap, finite, traj,
                        led, sent,
                    )
                    if blk is None:
                        continue  # depth-1 pipeline priming: no block
                        # to consume until the second dispatch
                else:
                    prev_state = state
                    with dispatch:
                        state, acc, traj = run_block(
                            state, acc, n_steps=n_steps,
                            record=do_record,
                            record_every=every if do_record else 1,
                        )
                    with _tracing.span("block.wait", tracer, trace_id,
                                       step=step):
                        sync(state.positions)
                    gap.completed()
                    consume.enter_context(_tracing.span(
                        "block.consume", tracer, trace_id, step=step,
                    ))
                    # Injected divergence (utils/faults.py): NaN the
                    # state so the watchdog below trips through its REAL
                    # detection path.
                    state = _faults.maybe_corrupt_state(
                        state, step, step + n_steps
                    )
                    last_good = prev_state
                    step += n_steps
                    led = (
                        self._ledger_fn(state) if ledger_on else None
                    )
                    sent = (
                        self._sentinel_fn(
                            state.positions, state.masses
                        )
                        if sent_every
                        and blocks_dispatched % sent_every == 0
                        else None
                    )
                    blocks_dispatched += 1
                    blk = (
                        step - n_steps, n_steps, state, None, traj,
                        led, sent,
                    )
            else:
                # Dispatching is done; drain the final in-flight block.
                blk, pending = pending, None

            # --- consume one finished block (k, while k+1 computes) ---
            prev_step, blk_steps, bstate, finite, traj, led, sent = blk
            end_step = prev_step + blk_steps
            finite_ok = True
            if pipelined:
                # Completion fence: a genuine value fetch (see
                # utils/timing.sync) — the watchdog verdict when the
                # watchdog is on, a scalar fence on the snapshot
                # otherwise. This is where the one-block watchdog lag
                # lives: block k's verdict is read while k+1 computes.
                with _tracing.span("block.wait", tracer, trace_id,
                                   step=prev_step):
                    if finite is not None:
                        finite_ok = bool(finite)
                    else:
                        sync(bstate.positions)
                gap.completed()
                consume.enter_context(_tracing.span(
                    "block.consume", tracer, trace_id, step=prev_step,
                ))
                if _faults.active() is not None:
                    # Injected divergence under the pipeline: the fault
                    # fires on the consumed snapshot (the forward state
                    # is already in flight), and the watchdog below
                    # aborts exactly as it would for a real NaN verdict.
                    corrupted = _faults.maybe_corrupt_state(
                        bstate, prev_step, end_step
                    )
                    if corrupted is not bstate:
                        finite_ok = False
                if not config.nan_check:
                    finite_ok = True
            elif config.nan_check:
                finite_ok = self._state_finite(bstate)
            if config.nan_check and not finite_ok:
                # Divergence watchdog (one block lagged under the
                # pipeline): abort with the last VERIFIED state
                # persisted rather than integrating garbage to the end.
                # Queued cadence saves drain first — Orbax drops
                # out-of-order steps. The emergency save stays
                # best-effort: a failing save (e.g. a foreign
                # conflicting snapshot in the dir) must not mask the
                # SimulationDiverged being raised.
                if checkpoint_manager is not None:
                    from .utils.checkpoint import save_checkpoint

                    try:
                        if host_writer is not None:
                            host_writer.barrier()
                        save_checkpoint(
                            checkpoint_manager, prev_step, last_good
                        )
                    except Exception as ce:  # noqa: BLE001
                        if logger is not None:
                            logger.log_print(
                                f"WARNING: emergency checkpoint at step "
                                f"{prev_step} failed: {ce}"
                            )
                if logger is not None:
                    logger.log_print(
                        f"DIVERGED within steps {prev_step + 1}.."
                        f"{end_step}; last finite state is at "
                        f"step {prev_step}"
                        + (" (checkpoint saved)"
                           if checkpoint_manager is not None else "")
                    )
                if telemetry is not None:
                    telemetry.recorder.record(
                        "event", event="diverged", step=prev_step,
                        end_step=end_step,
                    )
                    telemetry.recorder.dump("divergence")
                raise SimulationDiverged(prev_step)
            now = timer.mark()
            block_elapsed = now - block_prev
            block_prev = now
            if tracer is not None:
                # The solo twin of the serving `round` span: one span
                # per consumed block (the first one carries the
                # compile).
                t_wall = _time.time()
                tracer.emit(
                    "block", trace_id, t_wall - block_elapsed,
                    block_elapsed, steps_from=prev_step + 1,
                    steps_to=end_step,
                    compiled=(prev_step == start_step),
                )
            self.state, self._last_step = bstate, end_step
            if pipelined:
                last_good = bstate
            # Observatory consume: the companions dispatched with this
            # block are finished (the fence above proves the block is),
            # so these reads are cheap scalar fetches, not dispatches.
            drift = None
            if led is not None:
                ledger_last = self._ledger_convert(led)
                ledger_blocks += 1
                drift = diagnostics.ledger_drift(
                    ledger0, ledger_last,
                    com_frame=config.periodic_box <= 0.0,
                )
                drift_last = drift
                if drift["energy_drift"] is not None:
                    max_energy_drift = max(
                        max_energy_drift or 0.0, drift["energy_drift"]
                    )
            sent_summary = None
            if sent is not None:
                from .utils.profiling import sentinel_summary

                sent_summary = sentinel_summary(np.asarray(sent))
                if _faults.accuracy_breach_due(end_step):
                    # Injected solver overload: the breach workflow
                    # (event, dump, heal) runs through its real path.
                    sent_summary = dict(
                        sent_summary, p90_rel_err=1.0,
                        max_rel_err=1.0, injected=True,
                    )
                sent_stats["probes"] += 1
                sent_stats["last"] = sent_summary
                sent_stats["max_rel_err"] = max(
                    sent_stats["max_rel_err"] or 0.0,
                    sent_summary["max_rel_err"],
                )
                if tracer is not None:
                    # Provenance span: the probe itself ran inside the
                    # async window, so only the measured values are
                    # reportable, not a wall-clock extent.
                    tracer.emit(
                        "sentinel", trace_id, _time.time(), 0.0,
                        step=end_step, backend=self.backend,
                        median_rel_err=sent_summary["median_rel_err"],
                        p90_rel_err=sent_summary["p90_rel_err"],
                        max_rel_err=sent_summary["max_rel_err"],
                    )
            # Injected preemption: a real SIGTERM to this process, so the
            # handler -> SimulationPreempted -> checkpoint path below is
            # what actually gets exercised.
            _faults.maybe_preempt(prev_step, end_step)
            if logger is not None:
                logger.progress(end_step, total_steps)
            steps_since_merge_check += blk_steps
            # The final block always checks: the returned state must not
            # contain never-examined colliding pairs just because the
            # run length is not a multiple of merge_every. (merge_radius
            # > 0 resolves the pipeline off, so `state` is the live
            # consumed state here.)
            if (
                config.merge_radius > 0.0
                and (
                    steps_since_merge_check >= config.merge_every
                    or end_step >= total_steps
                )
            ):
                steps_since_merge_check = 0
                from .ops.encounters import (
                    merge_close_pairs,
                    merge_close_pairs_grid,
                    merge_scan_chunk,
                )

                # The pair scan needs every particle visible — illegal on
                # particle-sharded operands (an (N@shard, N@shard)
                # distance matrix has no legal sharding). Gather to
                # replicated for the check, reshard only if merged.
                merge_state = state
                if self.mesh is not None:
                    from .parallel import replicate_state, shard_state

                    merge_state = replicate_state(state, self.mesh)
                if state.n >= MERGE_GRID_THRESHOLD:
                    # Cell-grid candidate generation: O(N) detection —
                    # at the 2M merger the brute scan is ~2.2e12 pair
                    # checks per cadence; the grid is ~27*cap*N.
                    res = merge_close_pairs_grid(
                        merge_state, config.merge_radius,
                        k=config.merge_k, box=config.periodic_box,
                    )
                else:
                    # Exact O(N^2) chunked scan.
                    res = merge_close_pairs(
                        merge_state, config.merge_radius,
                        k=config.merge_k,
                        chunk=merge_scan_chunk(state.n),
                        box=config.periodic_box,
                    )
                if int(res.n_merged) > 0:
                    state = res.state
                    if self.mesh is not None:
                        state = shard_state(state, self.mesh)
                    self.state = state
                    merged_total += int(res.n_merged)
                    if logger is not None:
                        logger.log_print(
                            f"merged {int(res.n_merged)} pair(s) at step "
                            f"{end_step} ({merged_total} total)"
                        )
                    # Masses are traced through the block, so no retrace —
                    # just reseed the force carry from the merged state.
                    # Re-baseline the energy-drift metric: a merger
                    # physically dissipates kinetic energy, which is not
                    # integrator drift.
                    acc = init_carry(self.accel_fn, state)
                    self._e0 = None
                    if ledger_on:
                        # Re-baseline the ledger: a merger physically
                        # dissipates kinetic energy (and exchanges
                        # momentum with the removed tracer), which is
                        # not integrator drift. The merge path already
                        # synced, so this eager fetch is free.
                        ledger0 = self._ledger_convert(
                            self._ledger_fn(state)
                        )
            if metrics_logger is not None:
                from .utils.timing import pairs_per_step

                extra = {}
                if drift is not None:
                    # The in-program ledger's block record — the
                    # total_energy/energy_drift keys keep the old
                    # --metrics-energy stream schema; the momentum/
                    # angular-momentum/COM drifts are the new series
                    # (docs/observability.md "Numerics").
                    if ledger_last["energy"] is not None:
                        extra["total_energy"] = float(
                            ledger_last["energy"]
                        )
                    extra["energy_drift"] = drift["energy_drift"]
                    extra["momentum_drift"] = drift["momentum_drift"]
                    extra["angmom_drift"] = drift["angmom_drift"]
                    extra["com_drift"] = drift["com_drift"]
                if sent_summary is not None:
                    extra["force_err_median"] = sent_summary[
                        "median_rel_err"
                    ]
                    extra["force_err_p90"] = sent_summary[
                        "p90_rel_err"
                    ]
                # Only direct-sum backends report pairs_per_sec; fast
                # solvers do asymptotically less work than the dense
                # N*(N-1) count, so their rate carries the honest
                # dense_equiv_ label (utils/timing.pairs_metric_name).
                rate = (
                    pairs_per_step(self.n_real) * blk_steps / block_elapsed
                    if block_elapsed > 0 else None
                )
                extra[pairs_metric_name(self.backend)] = rate
                metrics_logger.log(
                    step=end_step,
                    block_steps=blk_steps,
                    block_s=block_elapsed,
                    **extra,
                )
            if trajectory_writer is not None and traj is not None:
                # Host transfer before slicing: slicing a sharded array on
                # device would force a resharding gather. Pipelined runs
                # block here on block k's D2H while k+1 computes; the
                # chunk writes themselves land on the background writer.
                traj_np = np.asarray(traj)[:, : self.n_real]
                for k in range(traj_np.shape[0]):
                    trajectory_writer.record(
                        prev_step + (k + 1) * every, traj_np[k]
                    )
            if checkpoint_manager is not None:
                from .utils.checkpoint import crossed_cadence

                if crossed_cadence(
                    prev_step, end_step, config.checkpoint_every
                ):
                    _save_cadence(end_step, bstate)
            if (
                sent_summary is not None
                and config.error_budget > 0.0
                and sent_summary["p90_rel_err"] > config.error_budget
            ):
                # Error-budget breach: raised AFTER this block's
                # trajectory/checkpoint writes so a supervised heal
                # continues a gap-free run from self._last_step. The
                # state is finite — the supervisor reroutes/re-sizes
                # rather than rolling back (docs/observability.md
                # "Numerics").
                if logger is not None:
                    logger.log_print(
                        f"ACCURACY BREACH at step {end_step}: "
                        f"{self.backend} sentinel p90 rel err "
                        f"{sent_summary['p90_rel_err']:.3e} > budget "
                        f"{config.error_budget:.3e}"
                    )
                if telemetry is not None:
                    telemetry.recorder.record(
                        "event", event="accuracy_breach",
                        step=end_step, backend=self.backend,
                        p90_rel_err=sent_summary["p90_rel_err"],
                        budget=config.error_budget,
                    )
                    telemetry.recorder.dump("accuracy_breach")
                raise AccuracyBreach(
                    end_step, self.backend,
                    sent_summary["p90_rel_err"], config.error_budget,
                )
          consume.close()
          # Normal completion: drain the background writer and close
          # the trajectory INSIDE the try so a failed trajectory/
          # checkpoint write fails the run instead of vanishing with
          # the thread.
          with _tracing.span("run.drain", tracer, trace_id):
            if host_writer is not None:
                host_writer.barrier()
            if trajectory_writer is not None:
                trajectory_writer.close()
        except KeyboardInterrupt as e:
            # Graceful interrupt OR preemption (SimulationPreempted is a
            # KeyboardInterrupt subclass): persist what we have so
            # `resume` works (the reference loses everything on any
            # interruption). self.state/_last_step name the last
            # CONSUMED block — a pipelined run's in-flight block is
            # dropped and re-integrated on resume. The queued cadence
            # saves drain first (Orbax drops out-of-order steps).
            if telemetry is not None:
                telemetry.recorder.record(
                    "event",
                    event=(
                        "preempted"
                        if isinstance(e, SimulationPreempted)
                        else "interrupted"
                    ),
                    step=self._last_step,
                )
                if isinstance(e, SimulationPreempted):
                    telemetry.recorder.dump("sigterm")
            if checkpoint_manager is not None and \
                    self._last_step > start_step:
                from .utils.checkpoint import save_checkpoint

                word = (
                    "Preempted (SIGTERM)"
                    if isinstance(e, SimulationPreempted)
                    else "Interrupted"
                )
                try:
                    if host_writer is not None:
                        host_writer.barrier()
                    save_checkpoint(
                        checkpoint_manager, self._last_step, self.state
                    )
                except Exception as ce:  # noqa: BLE001 — best-effort:
                    # a failed save must not mask the interrupt itself.
                    if logger is not None:
                        logger.log_print(
                            f"WARNING: {word} at step {self._last_step} "
                            f"but the checkpoint save failed: {ce}"
                        )
                else:
                    if logger is not None:
                        logger.log_print(
                            f"{word} at step {self._last_step}; "
                            "checkpoint saved"
                        )
            raise
        finally:
            consume.close()
            if host_writer is not None:
                # Error paths land here with an exception already
                # propagating: drain and stop the thread without
                # raising over it (barrier above covers success).
                host_writer.close(raise_errors=False)
        with _tracing.span("run.close", tracer, trace_id):
            timer.mark()

            self.state = state
            total_time = timer.total
            evals = FORCE_EVALS_PER_STEP[config.integrator]
            stats = throughput(
                self.n_real,
                total_steps - start_step,
                total_time,
                num_devices=self.mesh.size if self.mesh else 1,
                force_evals_per_step=evals,
            )
            # Close the gap accounting AFTER the final trajectory flush: the
            # tail-end host work (last block's writes, manifest, writer
            # drain) is device-idle time too.
            gap.finish()
            stats["io_pipeline"] = "on" if pipelined else "off"
            stats["donated"] = bool(self.donated)
            # Routing observability (docs/scaling.md "Autotuned routing"):
            # which backend actually ran, whether the autotune cache hit,
            # and what the probe cost — the run-stats half of the
            # acceptance contract (the BENCH JSON line carries the same).
            stats["backend"] = self.backend
            stats["autotune_cache"] = self.autotune["cache"]
            stats["autotune_probe_ms"] = self.autotune["probe_ms"]
            stats["host_gap_frac"] = gap.host_gap_frac
            stats["pair_symmetric_share"] = self.pair_symmetric_share
            # Performance observatory (docs/observability.md
            # "Performance"): the perf facts promoted into the run's
            # metrics registry when a telemetry bundle is attached — the
            # same gauge names the serving worker publishes, so solo and
            # served runs merge in one fleet view. The run's own
            # compiled-program rows ride along in stats["perf"].
            if telemetry is not None:
                from .telemetry import declare_worker_metrics

                reg = declare_worker_metrics(telemetry.registry)
                if gap.host_gap_frac is not None:
                    reg.gauge("gravity_host_gap_frac").set(
                        gap.host_gap_frac
                    )
                if total_time > 0:
                    reg.gauge("gravity_steps_per_sec").set(
                        (total_steps - start_step) / total_time
                    )
                if self.autotune["probe_ms"]:
                    reg.histogram("gravity_autotune_probe_ms").observe(
                        self.autotune["probe_ms"]
                    )
            from .telemetry import perf as _perf

            stats["perf"] = _perf.summarize_rows([
                r for r in _perf.ledger().rows_list()
                if r.get("key") == self._run_block.key
            ])
            if ledger_on:
                # The drift series' run-level summary (docs/observability
                # .md "Numerics") — consumed by the BENCH JSON line and the
                # cadence A/B alongside host_gap_frac.
                stats["ledger"] = {
                    "blocks": ledger_blocks,
                    "max_energy_drift": max_energy_drift,
                    **(drift_last or {}),
                }
                if ledger_last is not None \
                        and ledger_last["energy"] is not None:
                    stats["total_energy"] = float(ledger_last["energy"])
            if sent_every:
                stats["sentinel"] = {
                    "backend": self.backend,
                    "every": sent_every,
                    "k": int(self.config.sentinel_k),
                    "probes": sent_stats["probes"],
                    "max_rel_err": sent_stats["max_rel_err"],
                    **{
                        k: sent_stats["last"][k]
                        for k in ("median_rel_err", "p90_rel_err")
                        if sent_stats["last"] is not None
                    },
                }
            if trace_id is not None:
                stats["trace_id"] = trace_id
            if config.merge_radius > 0.0:
                stats["merged_pairs"] = merged_total
            return self._finish(logger, total_time, total_steps - start_step,
                                stats)

    def _banner(self, logger: Optional[RunLogger], steps: int,
                integrator_label: str) -> None:
        if logger is not None:
            logger.start_banner(
                num_devices=self.mesh.size if self.mesh else 1,
                num_particles=self.n_real,
                steps=steps,
                dt=self.config.dt,
                model=self.config.model,
                integrator=integrator_label,
                backend=self.backend,
                sharding=self.config.sharding,
                dtype=self.config.dtype,
            )

    @staticmethod
    def _state_finite(state: ParticleState) -> bool:
        return bool(
            jnp.all(jnp.isfinite(state.positions))
            & jnp.all(jnp.isfinite(state.velocities))
        )

    def _finish(self, logger: Optional[RunLogger], total_time: float,
                steps: int, stats: dict) -> dict:
        """Shared run epilogue: perf log, final positions, results dict."""
        stats["final_state"] = self.final_state()
        if logger is not None:
            logger.performance(
                total_time, steps, pairs_per_sec=stats["pairs_per_sec"]
            )
            logger.final_positions(
                np.asarray(stats["final_state"].positions)
            )
            logger.completed()
        if self.fmm_sparse:
            # Occupancy drift audit: the sparse sizing was fixed from
            # the INITIAL state; a run whose structure spread out can
            # exceed k_cells mid-flight, silently degrading the
            # rank-overflow cells to the monopole fallback. Eager
            # host-side count on the concrete final state — cheap, and
            # the honest signal the jitted path cannot emit.
            from .ops.sfmm import final_occupancy_check

            # The FULL padded array — the same point set the solver
            # binned (mesh padding starts coincident with particle 0
            # and drifts as zero-mass test bodies; excluding it could
            # under-count vs the solver's own occupancy).
            note = final_occupancy_check(
                stats["final_state"].positions, self.sfmm_sizing
            )
            stats["sfmm_final_occupancy"] = note
            if note["overflow"] and logger is not None:
                logger.log_print(
                    "WARNING: sparse-FMM occupancy grew past k_cells "
                    f"during the run ({note['occupied']} occupied vs "
                    f"k_cells={note['k_cells']} at depth "
                    f"{note['depth']}); rank-overflow cells degraded "
                    "to the monopole fallback — re-run with a larger "
                    "k_cells (or let auto re-size from a later state)"
                )
        return stats

    def run_adaptive(
        self,
        logger: Optional[RunLogger] = None,
        *,
        trajectory_writer: Optional[TrajectoryWriter] = None,
        checkpoint_manager=None,
        metrics_logger=None,
        start_t: float = 0.0,
        start_comp: float = 0.0,
        start_steps: int = 0,
    ) -> dict:
        """Adaptive-dt run to t_end = steps * dt (see ops.adaptive).

        Block-wise: an outer host loop drives bounded jitted
        ``lax.while_loop`` blocks (capped at ~progress_every steps), so
        trajectory/checkpoint/metrics stream at block boundaries exactly
        like fixed-dt runs — a long adaptive run is crash-resumable.
        Trajectory frames land at block boundaries (irregular simulated
        times; the metrics JSONL records t per block). Checkpoints store
        (t, kahan comp) as extras; ``resume`` passes them back via
        ``start_t``/``start_steps``. SIGTERM raises
        :class:`SimulationPreempted` through the same checkpoint-and-exit
        path as Ctrl-C.
        """
        with preemption_guard():
            return self._run_adaptive_impl(
                logger, trajectory_writer=trajectory_writer,
                checkpoint_manager=checkpoint_manager,
                metrics_logger=metrics_logger, start_t=start_t,
                start_comp=start_comp, start_steps=start_steps,
            )

    def _run_adaptive_impl(
        self,
        logger: Optional[RunLogger] = None,
        *,
        trajectory_writer: Optional[TrajectoryWriter] = None,
        checkpoint_manager=None,
        metrics_logger=None,
        start_t: float = 0.0,
        start_comp: float = 0.0,
        start_steps: int = 0,
    ) -> dict:
        from .ops.adaptive import adaptive_run

        config = self.config
        if config.merge_radius > 0.0:
            # Mirrors the CLI guard for Python-API callers: silently
            # dropping collision merging would change the physics.
            raise ValueError(
                "adaptive mode does not support collision merging "
                "(merge_radius > 0); use fixed-dt runs for merging"
            )
        t_end = config.steps * config.dt
        criterion = config.timestep_criterion
        if criterion == "auto":
            criterion = "accel" if config.eps > 0.0 else "velocity"
        if config.integrator not in ("euler", "leapfrog", "multirate"):
            # "euler" is only the config default, not a real request for
            # adaptive Euler; anything else would be silently ignored.
            raise ValueError(
                f"adaptive mode integrates with KDK leapfrog (or the "
                f"multirate rung ladder); integrator="
                f"{config.integrator!r} is not supported "
                "(use fixed-dt runs for verlet/yoshida4)"
            )
        if (
            config.integrator == "multirate"
            and self.mesh is not None
            and config.multirate_rungs > 2
        ):
            raise ValueError(
                "adaptive + multirate composition supports the two-rung "
                "scheme on a mesh (multirate_rungs=2); the sharded rung "
                "ladder stays fixed-dt for now"
            )

        # Adaptive x multirate composition: the adaptive criterion sizes
        # the OUTER dt each step, and the rung ladder subdivides it per
        # particle — the answer to the "one deeply bound binary drags
        # the whole system to its timestep" scaling wall (the multirate
        # step functions take dt as a runtime value, so they trace
        # straight into the adaptive while_loop).
        step_fn = None
        exclude_fastest = 0
        mode = "adaptive-kdk"
        if config.integrator == "multirate":
            from .ops.multirate import rung_ladder_step, two_rung_step

            k, capacities = self._multirate_plan()
            # The criterion sizes the outer step from the SLOW remainder
            # — without this exclusion the fastest particle still drags
            # the global dt and the ladder only adds work.
            exclude_fastest = k
            if self.mesh is not None:
                from .ops.multirate import two_rung_step_sharded

                # _build_fns prepared the sharded multirate kernels
                # (integrator == "multirate" and a mesh imply both).
                step_fn = partial(
                    two_rung_step_sharded, mesh=self.mesh,
                    rect_accel=self._rect_accel,
                    fast_fast=self._fast_fast_kernel,
                    accel_full=self._accel2, k=k,
                    n_sub=config.multirate_sub,
                )
                mode = (
                    f"adaptive-multirate sharded (k={k}, "
                    f"sub={config.multirate_sub})"
                )
            elif capacities is not None:
                step_fn = partial(
                    rung_ladder_step, accel_vs=self._local_vs_kernel,
                    capacities=capacities, accel_full=self._accel2,
                )
                mode = (
                    f"adaptive-multirate (rungs="
                    f"{config.multirate_rungs}, k={k})"
                )
            else:
                step_fn = partial(
                    two_rung_step, accel_vs=self._local_vs_kernel,
                    k=k, n_sub=config.multirate_sub,
                    accel_full=self._accel2,
                )
                mode = (
                    f"adaptive-multirate (k={k}, "
                    f"sub={config.multirate_sub})"
                )

        self._banner(
            logger, config.steps,
            f"{mode} ({criterion}, eta={config.eta})",
        )

        # Adaptive blocks make host-side control-flow decisions from each
        # block's (t, steps) result, so the compute loop stays serial —
        # but the host-I/O half of the pipeline still applies: trajectory
        # frames and checkpoint saves run on the background writer, with
        # the same hard barrier on divergence/interrupt/SIGTERM.
        host_writer, trajectory_writer, _submit_save = (
            self._make_host_pipeline(
                trajectory_writer, checkpoint_manager,
                self._resolve_io_pipeline(),
            )
        )

        block_cap = max(1, min(config.progress_every,
                               config.adaptive_max_steps))
        # max_steps is a static (trace-time) bound, so a shrunken final
        # block (to honor adaptive_max_steps exactly) compiles a second
        # while_loop — cache per distinct budget; at most two occur.
        _block_fns: dict = {}

        def run_block(st, *, budget, t0, comp0, acc0):
            if budget not in _block_fns:
                _block_fns[budget] = jax.jit(
                    partial(
                        adaptive_run,
                        accel_fn=self.accel_fn,
                        t_end=t_end,
                        dt_max=config.dt,
                        eta=config.eta,
                        eps=config.eps,
                        criterion=criterion,
                        max_steps=budget,
                        step_fn=step_fn,
                        exclude_fastest=exclude_fastest,
                    )
                )
            return _block_fns[budget](st, t0=t0, comp0=comp0, acc0=acc0)

        dtype = self.state.positions.dtype
        t_end_cast = float(jnp.asarray(t_end, dtype))

        timer = StepTimer()
        timer.start()
        block_prev = 0.0
        state = self.state
        t = start_t
        comp = start_comp
        # Seed the carried acceleration eagerly: passing acc0=None into
        # the jitted block would retrace it once acc becomes an array.
        acc = self.accel_fn(state.positions)
        steps_taken = start_steps
        dt_min = float("inf")
        dt_max_used = 0.0
        # One consistent (state, steps, t, comp) snapshot, updated in a
        # single assignment once a block is known finite — the ONLY
        # source for checkpoints, so an interrupt or divergence can
        # never pair a stale state with a newer simulated time.
        snap = (state, steps_taken, t, comp)
        # Mirrored on self per block so the supervisor can resume a
        # transient-failed adaptive run from the in-memory state instead
        # of rolling back to (or past) the last checkpoint.
        self._snap = snap
        self._last_step = steps_taken
        try:
          while (
              t < t_end_cast
              and steps_taken < config.adaptive_max_steps
          ):
            _faults.maybe_raise_transient(steps_taken)
            prev_steps = steps_taken
            budget = min(block_cap,
                         config.adaptive_max_steps - steps_taken)
            res = run_block(state, budget=budget, t0=t, comp0=comp,
                            acc0=acc)
            sync(res.state.positions)
            state, acc = res.state, res.acc
            t, comp = float(res.t), float(res.comp)
            block_steps = int(res.steps)
            state = _faults.maybe_corrupt_state(
                state, prev_steps, prev_steps + block_steps
            )
            if block_steps > 0:
                dt_min = min(dt_min, float(res.dt_min))
                dt_max_used = max(dt_max_used, float(res.dt_max_used))
            if config.nan_check and not self._state_finite(state):
                if checkpoint_manager is not None and snap[1] > 0:
                    from .utils.checkpoint import save_checkpoint

                    try:
                        if host_writer is not None:
                            # Queued cadence saves land first (Orbax
                            # drops out-of-order steps).
                            host_writer.barrier()
                        save_checkpoint(
                            checkpoint_manager, snap[1], snap[0],
                            extra={"t": snap[2], "comp": snap[3]},
                        )
                    except Exception as ce:  # noqa: BLE001 — must not
                        # mask the SimulationDiverged being raised.
                        if logger is not None:
                            logger.log_print(
                                f"WARNING: emergency checkpoint at "
                                f"step {snap[1]} failed: {ce}"
                            )
                if logger is not None:
                    logger.log_print(
                        f"DIVERGED during adaptive run (after "
                        f"{steps_taken} steps)"
                    )
                raise SimulationDiverged(steps_taken)
            now = timer.mark()
            block_elapsed = now - block_prev
            block_prev = now
            steps_taken += block_steps
            snap = (state, steps_taken, t, comp)
            self._snap = snap
            self.state, self._last_step = state, steps_taken
            _faults.maybe_preempt(prev_steps, steps_taken)
            if logger is not None:
                logger.log_print(
                    f"t={t:.6g}/{t_end:.6g} ({steps_taken} adaptive "
                    f"steps, dt in [{float(res.dt_min):.3g}, "
                    f"{float(res.dt_max_used):.3g}])"
                )
            if metrics_logger is not None:
                from .utils.timing import pairs_metric_name, pairs_per_step

                metrics_logger.log(
                    step=steps_taken,
                    block_steps=block_steps,
                    block_s=block_elapsed,
                    t=t,
                    dt_min=float(res.dt_min) if block_steps else None,
                    dt_max=float(res.dt_max_used) if block_steps else None,
                    **{pairs_metric_name(self.backend): (
                        pairs_per_step(self.n_real) * block_steps
                        / block_elapsed
                        if block_elapsed > 0 else None
                    )},
                )
            if trajectory_writer is not None and block_steps > 0:
                frame = np.asarray(
                    jax.device_get(state.positions)
                )[: self.n_real]
                trajectory_writer.record(steps_taken, frame)
            if checkpoint_manager is not None:
                from .utils.checkpoint import crossed_cadence
            if checkpoint_manager is not None and crossed_cadence(
                prev_steps, steps_taken, config.checkpoint_every
            ):
                _submit_save(steps_taken, state, {"t": t, "comp": comp})
            if block_steps == 0:
                break  # t >= t_end in state dtype; nothing advanced
          # Normal completion: surface background I/O failures while
          # still inside the try (the finally below only cleans up).
          if host_writer is not None:
            host_writer.barrier()
        except KeyboardInterrupt as e:
            if checkpoint_manager is not None and snap[1] > start_steps:
                from .utils.checkpoint import save_checkpoint

                word = (
                    "Preempted (SIGTERM)"
                    if isinstance(e, SimulationPreempted)
                    else "Interrupted"
                )
                try:
                    if host_writer is not None:
                        host_writer.barrier()
                    save_checkpoint(
                        checkpoint_manager, snap[1], snap[0],
                        extra={"t": snap[2], "comp": snap[3]},
                    )
                except Exception as ce:  # noqa: BLE001 — best-effort:
                    # a failed save must not mask the interrupt itself.
                    if logger is not None:
                        logger.log_print(
                            f"WARNING: {word} at adaptive step "
                            f"{snap[1]} but the checkpoint save "
                            f"failed: {ce}"
                        )
                else:
                    if logger is not None:
                        logger.log_print(
                            f"{word} at adaptive step {snap[1]} "
                            f"(t={snap[2]:.6g}); checkpoint saved"
                        )
            raise
        finally:
            if host_writer is not None:
                host_writer.close(raise_errors=False)
        timer.mark()

        if config.periodic_box > 0.0:
            # Same fp-health re-wrap the block loop applies (forces are
            # wrap-invariant; mid-run coordinates may exceed the box).
            box = jnp.asarray(config.periodic_box, state.positions.dtype)
            state = state.replace(positions=jnp.mod(state.positions, box))
            self.state = state

        if trajectory_writer is not None:
            trajectory_writer.close()

        run_steps = steps_taken - start_steps
        stats = throughput(
            self.n_real,
            max(run_steps, 1),
            timer.total,
            num_devices=self.mesh.size if self.mesh else 1,
        )
        stats.update(
            backend=self.backend,
            autotune_cache=self.autotune["cache"],
            autotune_probe_ms=self.autotune["probe_ms"],
            t_end=t_end,
            t_reached=t,
            adaptive_steps=steps_taken,
            dt_min=dt_min if dt_min != float("inf") else None,
            dt_max_used=dt_max_used,
            criterion=criterion,
        )
        if steps_taken >= config.adaptive_max_steps and logger is not None:
            logger.log_print(
                f"WARNING: max_steps={config.adaptive_max_steps} hit at "
                f"t={t:.6g} of {t_end:.6g}"
            )
        return self._finish(logger, timer.total, run_steps, stats)

    def final_state(self) -> ParticleState:
        """State restricted to the real (unpadded) particles, on host-default
        placement (device_get avoids sharded-slice resharding)."""
        with _tracing.span("run.final_state"):
            s = jax.device_get(self.state)
            return ParticleState(
                positions=jnp.asarray(s.positions[: self.n_real]),
                velocities=jnp.asarray(s.velocities[: self.n_real]),
                masses=jnp.asarray(s.masses[: self.n_real]),
            )

    def energy(self):
        """Total conserved energy: kinetic + self-gravity potential +
        (when configured) the external field's potential energy — so the
        drift metric keeps measuring integrator health under
        --external."""
        state = self.final_state()
        config = self.config
        if config.periodic_box > 0.0:
            # The isolated pairwise potential is not conserved in a
            # periodic box (and jumps at re-wraps); use the mesh
            # potential the solver actually integrates.
            from .ops.diagnostics import kinetic_energy
            from .ops.periodic import pm_periodic_potential_energy

            e = kinetic_energy(state) + pm_periodic_potential_energy(
                state.positions, state.masses, box=config.periodic_box,
                grid=config.pm_grid, g=config.g, eps=config.eps,
                assignment=config.pm_assignment,
            )
        elif (
            self.backend in ("tree", "fmm", "sfmm", "p3m")
            and self.n_real > ENERGY_TREE_THRESHOLD
        ):
            # Scale-aware diagnostic: the dense pair scan costs ~5.5e11
            # pair evaluations at 1M bodies — more than the force step it
            # monitors. Above small N, a fast-solver run prices its energy
            # sample with the same O(N log N) machinery (tree monopole
            # potential; P3M runs use it too — same isolated-BC physics).
            from .ops.diagnostics import kinetic_energy_f64
            from .ops.tree import recommended_depth_data, tree_potential_energy

            # Resolve the depth once per run (host np.unique passes over
            # N ids are not free at 1M, and a depth change mid-run would
            # recompile the PE kernel inside the metrics path).
            depth = getattr(self, "_energy_tree_depth", None)
            if depth is None:
                depth = config.tree_depth or recommended_depth_data(
                    state.positions, config.tree_leaf_cap
                )
                self._energy_tree_depth = depth
            # Host-f64 sum: the scalable PE functions return np.float64
            # precisely because |PE| can exceed fp32 range; adding a
            # jnp f32 KE would demote the whole thing back to f32.
            # On TPU the gather-free FMM potential carries the sample
            # (the tree PE's per-target interaction-list gathers are
            # the access pattern the chip measured index-rate-bound);
            # on CPU the tree PE stays the measured-fast choice.
            if jax.devices()[0].platform == "tpu":
                from .ops.fmm import fmm_potential_energy

                pe = fmm_potential_energy(
                    state.positions, state.masses, depth=depth,
                    leaf_cap=config.tree_leaf_cap, ws=config.tree_ws,
                    g=config.g, cutoff=config.cutoff, eps=config.eps,
                )
            else:
                pe = tree_potential_energy(
                    state.positions, state.masses, depth=depth,
                    leaf_cap=config.tree_leaf_cap, ws=config.tree_ws,
                    chunk=config.fast_chunk, g=config.g,
                    cutoff=config.cutoff, eps=config.eps,
                )
            e = kinetic_energy_f64(state) + pe
        else:
            e = diagnostics.total_energy(
                state, g=config.g, cutoff=config.cutoff, eps=config.eps,
            )
        if self._ext_phi is not None:
            ext_e = jnp.sum(state.masses * self._ext_phi(state.positions))
            if isinstance(e, np.floating):
                # Keep the host-f64 accumulation (tree/p3m branch) —
                # jnp's weak promotion would demote f64 + f32 to f32.
                e = e + np.float64(jax.device_get(ext_e))
            else:
                e = e + ext_e
        return e
