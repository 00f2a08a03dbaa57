"""Tiled Pallas TPU kernels for direct-sum pairwise gravity.

TPU-native redesign of the reference CUDA kernel
(`/root/reference/cuda.cu:32-60`). The CUDA kernel is one-thread-per-
particle over j>i pairs — severely load-imbalanced (thread 0 does N-1
pairs, thread N-1 does none) and with an unsynchronized cross-thread write
to ``forces[3j]`` (`cuda.cu:47-49`). Two kernels here:

- :func:`pallas_accelerations_vs` (targets vs sources: the ring's hops,
  the multirate kicks, serving). FlashAttention-style tiling: grid over
  (i-tile, j-tile); the (N, N) interaction matrix is never materialized.
  j is the minor grid axis, so each i-tile's accumulator block stays
  VMEM-resident across the j-stream. Every tile does identical work (full
  rectangular tile) and accumulates into its block-private accumulator.
- :func:`pallas_pairwise_accelerations` (targets == sources). Newton's
  third law, as the reference's own j>i loop uses it: each unordered
  pair of square (T, T) tiles is evaluated once, over the upper triangle
  of tiles, and serves both bodies: +w_ij·d_ij into body i's row sums,
  -w_ji·d_ij into body j's column sums. Its pair pipeline runs on
  (16, 128) blocks, two vregs, and is VALU-bound: 35 VALU ops and one
  rsqrt per unordered pair, ~17.5 per directed pair (counted in the
  v5e compiler's bundles). The race the reference had on
  ``forces[3j]`` cannot happen: the grid runs sequentially on the
  core's one TensorCore ("arbitrary" semantics), the row sums of tile
  row i are one output block written back once, and the column sums are
  one VMEM-resident block that only the current grid step touches.
  Above :data:`SYM_MAX_N` the column accumulator no longer fits in VMEM
  and the entry runs the rectangular kernel instead.
- Mixed layout: target positions are fed as (TI, 3) row-blocks (columns
  sliced to (TI, 1) vectors), source positions as a transposed (3, N) array
  so j-tiles are (3, TJ) with the long axis on lanes — both broadcast
  cleanly to the (TI, TJ) VPU tiles that carry the pair pipeline. The
  symmetric kernel reads both tiles this way, with G·m as a fourth column
  (row) beside the positions.

The wrappers pad N to tile multiples with zero-mass bodies (exact: zero
mass contributes zero weight, on either side of a pair) and slice the
real bodies back.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..constants import CUTOFF_RADIUS, G

# Default tile sizes, tuned on a real v5e chip (2026-07): (512, 2048) and
# (1024, 1024) tie at ~1.6e11 pairs/s/chip; (TI, TJ) f32 intermediates at
# 512x2048 are 4 MB each, comfortably inside VMEM. (512, 4096) fails to
# compile (VMEM), so don't raise TILE_J further.
TILE_I = 512
TILE_J = 2048


def _nbody_kernel(xi_ref, xjt_ref, gmj_ref, acc_ref, *, cutoff, eps, masked):
    """One (i-tile, j-tile) block of the pairwise-acceleration sum.

    `masked` is a trace-time Python bool selecting between two
    specializations of the same math:

    - masked=True — the general path: below-cutoff pairs (incl. the r == 0
      self-pair) get zero weight; the where() on the rsqrt input keeps it
      finite so no NaN ever forms.
    - masked=False — the mask-free fast path, valid whenever eps² > cutoff²:
      softening makes the cutoff branch dead code (r²+eps² ≥ eps² > cutoff²),
      the self-pair contributes exactly zero through dx=dy=dz=0, and
      zero-mass padded sources through G·m_j = 0. Dropping the compare + two
      selects cuts ~3 of ~22 VPU ops per pair (+17% measured on v5e,
      bit-identical output).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = xi_ref[...]  # (TI, 3) targets
    xjt = xjt_ref[...]  # (3, TJ) sources, transposed
    gmj = gmj_ref[...]  # (1, TJ) pre-multiplied G·m_j

    dx = xjt[0:1, :] - xi[:, 0:1]  # (TI, TJ)
    dy = xjt[1:2, :] - xi[:, 1:2]
    dz = xjt[2:3, :] - xi[:, 2:3]
    dtype = dx.dtype
    r2_soft = dx * dx + dy * dy + dz * dz + jnp.asarray(eps * eps, dtype)

    # fp32 ordering in both branches: inv_r**3 alone underflows (subnormal
    # flush) for r > ~2e12 m, zeroing distant pairs — fold G·m_j in first.
    if masked:
        valid = r2_soft > jnp.asarray(cutoff * cutoff, dtype)
        safe = jnp.where(valid, r2_soft, jnp.asarray(1.0, dtype))
        inv_r = jax.lax.rsqrt(safe)
        w = jnp.where(valid, ((gmj * inv_r) * inv_r) * inv_r,
                      jnp.asarray(0.0, dtype))  # (TI, TJ)
    else:
        inv_r = jax.lax.rsqrt(r2_soft)
        w = ((gmj * inv_r) * inv_r) * inv_r  # (TI, TJ)

    ax = jnp.sum(w * dx, axis=1, keepdims=True)  # (TI, 1)
    ay = jnp.sum(w * dy, axis=1, keepdims=True)
    az = jnp.sum(w * dz, axis=1, keepdims=True)
    acc_ref[...] += jnp.concatenate([ax, ay, az], axis=1)  # (TI, 3)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@partial(
    jax.jit,
    static_argnames=("g", "cutoff", "eps", "tile_i", "tile_j", "interpret"),
)
def pallas_accelerations_vs(
    pos_i: jax.Array,
    pos_j: jax.Array,
    masses_j: jax.Array,
    *,
    g: float = G,
    cutoff: float = CUTOFF_RADIUS,
    eps: float = 0.0,
    tile_i: int = TILE_I,
    tile_j: int = TILE_J,
    interpret: bool = False,
) -> jax.Array:
    """Accelerations on targets `pos_i` (M, 3) from sources `pos_j` (K, 3).

    Same contract as :func:`gravity_tpu.ops.forces.accelerations_vs`, so it
    drops into the sharded allgather/ring strategies as the local kernel.
    ``interpret=True`` runs the Pallas interpreter (CPU testing).
    """
    m, k = pos_i.shape[0], pos_j.shape[0]
    dtype = pos_i.dtype
    tile_i = min(tile_i, _round_up(m, 8))
    tile_j = min(tile_j, _round_up(k, 128))
    mp = _round_up(m, tile_i)
    kp = _round_up(k, tile_j)

    pos_i_p = jnp.zeros((mp, 3), dtype).at[:m].set(pos_i)
    # Zero-mass padded sources are exact no-ops regardless of position.
    pos_jt = jnp.zeros((3, kp), dtype).at[:, :k].set(pos_j.T)

    gmj = jnp.zeros((1, kp), dtype).at[0, :k].set(
        jnp.asarray(g, dtype) * masses_j
    )

    grid = (mp // tile_i, kp // tile_j)
    # eps and cutoff are static floats, so this specialization is resolved
    # at trace time: softening dominating the cutoff makes the mask dead.
    kernel = functools.partial(
        _nbody_kernel, cutoff=cutoff, eps=eps,
        masked=eps * eps <= cutoff * cutoff,
    )
    flops_per_pair = 20
    acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_i, 3), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, tile_j), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_j), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_i, 3), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, 3), dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops_per_pair * mp * kp,
            bytes_accessed=(mp * 3 + 2 * kp * 4) * 4,
            transcendentals=mp * kp,  # rsqrt
        ),
        interpret=interpret,
    )(pos_i_p, pos_jt, gmj)
    return acc[:m]


def _sym_tile(nb, p, q):
    """Tile (i, j), i <= j, of step (p, q) of the symmetric grid.

    Grid row p holds triangle rows p and nb-1-p (nb - p and p + 1 tiles:
    nb + 1 together), each starting at its diagonal tile, so every tile
    row is one contiguous run of steps and the grid is a rectangle of
    plain index arithmetic (no tile list in SMEM, which grows as N²).
    """
    first = q < nb - p
    i = jax.lax.select(first, p, nb - 1 - p)
    j = jax.lax.select(first, p + q, q - 1)
    return i, j


# The symmetric kernel's pair pipeline runs on (BLOCK, LANES) blocks:
# BLOCK bodies of tile i on sublanes by LANES bodies of tile j on lanes,
# two f32 vregs per value, so that its live set stays in the register
# file (64 vregs on the v5e) and no value of it is a whole tile. A loop
# step unrolls GROUP rows of blocks: fewer leave the VALU slots idle at
# each step's ends, more make the kernel slower to trace and lower
# (both paid by every process that runs it).
LANES = 128
BLOCK = 16
GROUP = 512


def _nbody_sym_kernel(bi_ref, bjt_ref, acc_ref, col_ref, bib_ref, rowp_ref,
                      *, nb, tile, cutoff, eps, masked):
    """One tile pair (i <= j) of the symmetric all-pairs sum.

    ``bi_ref`` (T, 4) and ``bjt_ref`` (4, T) hold x, y, z and G·m of
    tiles i and j. Each tile row starts at its diagonal tile, which holds
    both directions of every pair in it and so adds to the row sums
    only. An off-diagonal tile adds +w_ij·d to the row sums of tile i
    and w_ji·d to the column sums of tile j, which the wrapper subtracts.
    `masked` as in :func:`_nbody_kernel`; the mask is symmetric, so one
    zeroed inv_r serves both sides.

    The tile is walked one lane chunk of tile j at a time, and in each
    chunk block by block down tile i. A chunk carries its column partial
    sums in vregs and reduces them across sublanes once; the diagonal
    tile computes them too and drops them. The row partial sums add up
    in ``rowp_ref`` (3, T, LANES) over the whole tile row and are
    reduced across lanes at its last tile; ``bib_ref`` (4, T, LANES)
    holds tile i broadcast over the lanes, made at the row's first tile.
    """
    i, j = _sym_tile(nb, pl.program_id(0), pl.program_id(1))
    dtype = acc_ref.dtype

    # Every column tile j > 0 is first touched by tile row 0, the grid's
    # first, which sets it; tile 0 is no off-diagonal tile's column.
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        col_ref[:, pl.ds(0, tile)] = jnp.zeros((3, tile), dtype)

    # The pipeline is unrolled over many blocks, so it is written in lax
    # ops: jnp's dispatch would dominate the kernel's trace time.
    add, mul, sub = jax.lax.add, jax.lax.mul, jax.lax.sub
    full = functools.partial(jax.lax.full, (BLOCK, LANES), dtype=dtype)
    eps2, cut2, one, zero = (full(v) for v in (eps * eps, cutoff * cutoff,
                                               1.0, 0.0))

    def pair(bi, bj):
        """w_ij·d and w_ji·d of one (BLOCK, LANES) block."""
        d = [sub(bj[k], bi[k]) for k in range(3)]
        dx, dy, dz = d
        r2_soft = add(add(add(mul(dx, dx), mul(dy, dy)), mul(dz, dz)), eps2)
        if masked:
            valid = jax.lax.gt(r2_soft, cut2)
            inv_r = jax.lax.select(
                valid, jax.lax.rsqrt(jax.lax.select(valid, r2_soft, one)),
                zero)
        else:
            inv_r = jax.lax.rsqrt(r2_soft)
        inv_r2 = mul(inv_r, inv_r)
        # G·m folded in before the powers of inv_r, as in _nbody_kernel:
        # inv_r**3 alone underflows for r > ~2e12 m; inv_r**2 holds to
        # 1e19 m.
        w_i = mul(mul(bj[3], inv_r), inv_r2)  # pull of j on i
        w_j = mul(mul(bi[3], inv_r), inv_r2)  # pull of i on j, along -d
        return [mul(w_i, dk) for dk in d], [mul(w_j, dk) for dk in d]

    group = math.gcd(GROUP, tile)

    def chunk(c, carry):
        at = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
        bj = [jnp.broadcast_to(bjt_ref[k:k + 1, at], (BLOCK, LANES))
              for k in range(4)]

        def rows(g, cols):
            # Static offsets from one base, so the compiler sees that the
            # blocks' loads and stores do not overlap.
            at_i = pl.ds(pl.multiple_of(g * group, group), group)
            bib, rowp = bib_ref.at[:, at_i, :], rowp_ref.at[:, at_i, :]
            for r in range(0, group, BLOCK):
                f_i, f_j = pair([bib[k, r:r + BLOCK, :] for k in range(4)],
                                bj)
                for k in range(3):
                    rowp[k, r:r + BLOCK, :] = add(rowp[k, r:r + BLOCK, :],
                                                  f_i[k])
                cols = [add(a, b) for a, b in zip(cols, f_j)]
            return cols

        cols = jax.lax.fori_loop(0, tile // group, rows, [zero] * 3)
        cols = jnp.concatenate(
            [jnp.sum(v, axis=0, keepdims=True) for v in cols], axis=0)

        @pl.when(i != j)
        def _():
            at_j = pl.ds(pl.multiple_of(j * tile + c * LANES, LANES), LANES)
            col_ref[:, at_j] = jnp.where(
                i == 0, cols, col_ref[:, at_j] + cols)  # (3, LANES)

        return carry

    @pl.when(i == j)
    def _():
        def fill(s, carry):
            rs = pl.ds(pl.multiple_of(s * 8, 8), 8)
            b = bi_ref[rs, :]
            for k in range(4):
                bib_ref[k, rs, :] = jnp.broadcast_to(b[:, k:k + 1], (8, LANES))
            for k in range(3):
                rowp_ref[k, rs, :] = jnp.zeros((8, LANES), dtype)
            return carry

        jax.lax.fori_loop(0, tile // 8, fill, 0)

    jax.lax.fori_loop(0, tile // LANES, chunk, 0)

    @pl.when(j == nb - 1)
    def _():
        def put(s, carry):
            rs = pl.ds(pl.multiple_of(s * 8, 8), 8)
            acc_ref[rs, :] = jnp.concatenate(
                [jnp.sum(rowp_ref[k, rs, :], axis=1, keepdims=True)
                 for k in range(3)], axis=1)
            return carry

        jax.lax.fori_loop(0, tile // 8, put, 0)


# VMEM (v5e: 128 MiB per TensorCore) the symmetric kernel may claim: the
# column accumulator, one (3, N) block in (4, 128) tiles (16 B/body), and
# the tile's working set: the (T, 4) and (T, 3) blocks, double-buffered
# and lane-padded, and the two (·, T, 128) scratch arrays, 11.0 MiB at
# T = 2048 by compiling for the v5e. 4,456,448 bodies: every run
# autotune sends to the direct sum.
SYM_VMEM_BYTES = 100 * 2**20
SYM_TILE_VMEM_BYTES = 32 * 2**20
SYM_MAX_N = (SYM_VMEM_BYTES - SYM_TILE_VMEM_BYTES) // 16


def symmetric_tile(n: int, tile_i: int = TILE_I,
                   tile_j: int = TILE_J) -> int | None:
    """The square tile of the symmetric kernel for ``n`` bodies, or None
    where ``n`` takes the rectangular kernel (above :data:`SYM_MAX_N`).

    The tile is as wide as the wider of ``tile_i`` and ``tile_j`` (the
    defaults give 2048, of 512, 1024 and 2048 the fastest per evaluation
    at 262,144 bodies on a v5e), at least 128 lanes, and at most half of
    ``n`` rounded up, so the grid has an even number of tile rows, at
    least two.
    """
    if n > SYM_MAX_N:
        return None
    tile = _round_up(max(tile_i, tile_j), 128)
    return min(tile, _round_up(-(-n // 2), 128))


def pair_symmetric_share(n: int, tile_i: int = TILE_I,
                         tile_j: int = TILE_J) -> float:
    """Share of the pair tiles :func:`pallas_pairwise_accelerations`
    evaluates for ``n`` bodies that serve both of their bodies: (nb-1) /
    (nb+1) over nb tile rows; 0 where the rectangular kernel runs."""
    tile = symmetric_tile(n, tile_i, tile_j)
    if tile is None:
        return 0.0
    nb = _round_up(-(-n // tile), 2)
    return (nb - 1) / (nb + 1)


@partial(
    jax.jit,
    static_argnames=("g", "cutoff", "eps", "tile_i", "tile_j", "interpret"),
)
def pallas_pairwise_accelerations(
    positions: jax.Array,
    masses: jax.Array,
    *,
    g: float = G,
    cutoff: float = CUTOFF_RADIUS,
    eps: float = 0.0,
    tile_i: int = TILE_I,
    tile_j: int = TILE_J,
    interpret: bool = False,
) -> jax.Array:
    """All-pairs accelerations (targets == sources), each unordered pair
    evaluated once; the rectangular kernel above :data:`SYM_MAX_N`."""
    n = positions.shape[0]
    tile = symmetric_tile(n, tile_i, tile_j)
    if tile is None:
        return pallas_accelerations_vs(
            positions, positions, masses,
            g=g, cutoff=cutoff, eps=eps,
            tile_i=tile_i, tile_j=tile_j, interpret=interpret,
        )
    dtype = positions.dtype
    nb = _round_up(-(-n // tile), 2)
    npad = nb * tile
    bodies = (
        jnp.zeros((npad, 4), dtype)
        .at[:n, :3].set(positions)
        .at[:n, 3].set(jnp.asarray(g, dtype) * masses)
    )
    kernel = functools.partial(
        _nbody_sym_kernel, nb=nb, tile=tile, cutoff=cutoff, eps=eps,
        masked=eps * eps <= cutoff * cutoff,
    )
    tiles = nb * (nb + 1) // 2
    rows, cols = pl.pallas_call(
        kernel,
        grid=(nb // 2, nb + 1),
        in_specs=[
            pl.BlockSpec((tile, 4), lambda p, q: (_sym_tile(nb, p, q)[0], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, tile), lambda p, q: (0, _sym_tile(nb, p, q)[1]),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, 3), lambda p, q: (_sym_tile(nb, p, q)[0], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, npad), lambda p, q: (0, 0),
                         memory_space=pltpu.VMEM,
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, 3), dtype),
            jax.ShapeDtypeStruct((3, npad), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, tile, LANES), dtype),
            pltpu.VMEM((3, tile, LANES), dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=SYM_VMEM_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=26 * tiles * tile * tile,
            bytes_accessed=(nb * tile * 4 + tiles * 4 * tile
                            + npad * 6) * 4,
            transcendentals=tiles * tile * tile,  # rsqrt
        ),
        interpret=interpret,
    )(bodies, bodies.T)
    return (rows - cols.T)[:n]


def make_pallas_pairwise_kernel(
    *, g: float = G, cutoff: float = CUTOFF_RADIUS, eps: float = 0.0,
    interpret: bool = False,
):
    """(positions, masses) -> accelerations through
    :func:`pallas_pairwise_accelerations`, differentiable as
    :func:`make_pallas_local_kernel` is."""
    from .forces import wrap_with_dense_vjp

    def _forward(pos_i, pos_j, masses_j):
        del pos_j  # the same array as pos_i
        return pallas_pairwise_accelerations(
            pos_i, masses_j, g=g, cutoff=cutoff, eps=eps,
            interpret=interpret,
        )

    kernel = wrap_with_dense_vjp(_forward, g=g, cutoff=cutoff, eps=eps)
    return lambda pos, m: kernel(pos, pos, m)


def make_pallas_local_kernel(
    *, g: float = G, cutoff: float = CUTOFF_RADIUS, eps: float = 0.0,
    tile_i: int = TILE_I, tile_j: int = TILE_J, interpret: bool = False,
):
    """A LocalKernel closure for the sharded strategies.

    Differentiable via :func:`ops.forces.wrap_with_dense_vjp`
    (pallas_call has no autodiff rule; the backward runs the dense jnp
    math of the same force contract).
    """
    from .forces import wrap_with_dense_vjp

    def _forward(pos_i, pos_j, masses_j):
        return pallas_accelerations_vs(
            pos_i, pos_j, masses_j,
            g=g, cutoff=cutoff, eps=eps,
            tile_i=tile_i, tile_j=tile_j, interpret=interpret,
        )

    return wrap_with_dense_vjp(_forward, g=g, cutoff=cutoff, eps=eps)
