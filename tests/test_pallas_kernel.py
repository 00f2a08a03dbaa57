"""Pallas force kernels vs the jnp reference kernel (interpret mode on CPU).

The debug-mode race check from SURVEY §5: the pure-jnp kernel is the ground
truth the Pallas kernels must match (the TPU analog of running
compute-sanitizer against the CUDA kernel — except here divergence is the
only possible failure class, since one writer per accumulator makes the
reference's `forces[3j]` race impossible by construction). The all-pairs
entry evaluates each pair of tiles once (Newton's third law); it must
match both the dense sum and the rectangular kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gravity_tpu.ops.forces import (
    accelerations_vs,
    pairwise_accelerations_dense,
)
from gravity_tpu.ops import pallas_forces
from gravity_tpu.ops.pallas_forces import (
    SYM_MAX_N,
    pair_symmetric_share,
    pallas_accelerations_vs,
    pallas_pairwise_accelerations,
    symmetric_tile,
)


def _random_system(key, n, dtype=jnp.float32):
    kp, km = jax.random.split(key)
    pos = jax.random.uniform(kp, (n, 3), dtype, minval=-3e11, maxval=3e11)
    masses = jax.random.uniform(km, (n,), dtype, minval=1e23, maxval=1e25)
    return pos, masses


@pytest.mark.parametrize("n", [64, 256, 1000])
def test_matches_dense_jnp(key, n):
    """Pallas == dense jnp within fp32 tolerance (incl. non-tile-aligned N)."""
    pos, masses = _random_system(key, n)
    expected = pairwise_accelerations_dense(pos, masses)
    got = pallas_pairwise_accelerations(
        pos, masses, tile_i=32, tile_j=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-5, atol=1e-12
    )


def test_rectangular_targets_sources(key):
    pos, masses = _random_system(key, 384)
    expected = accelerations_vs(pos[:100], pos, masses)
    got = pallas_accelerations_vs(
        pos[:100], pos, masses, tile_i=32, tile_j=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-5, atol=1e-12
    )


def test_cutoff_semantics(key):
    """Coincident particles produce zero force and no NaNs in the kernel."""
    pos = jnp.zeros((16, 3), jnp.float32)  # all coincident -> all r=0
    masses = jnp.full((16,), 1e30, jnp.float32)
    acc = pallas_pairwise_accelerations(
        pos, masses, tile_i=8, tile_j=128, interpret=True
    )
    assert bool(jnp.all(jnp.isfinite(acc)))
    np.testing.assert_array_equal(np.asarray(acc), 0.0)


def test_softening(key):
    pos, masses = _random_system(key, 128)
    eps = 1e10
    expected = pairwise_accelerations_dense(pos, masses, eps=eps)
    got = pallas_pairwise_accelerations(
        pos, masses, eps=eps, tile_i=32, tile_j=128, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-5, atol=1e-12
    )


def test_softened_fast_path_self_pairs_and_padding(key):
    """The mask-free softened kernel (eps² > cutoff²) stays exact for the
    cases the dropped mask used to guard: self-pairs (zero via dx=0),
    coincident particles (finite via eps), and zero-mass tile padding."""
    pos, masses = _random_system(key, 200)
    pos = pos.at[:4].set(pos[0])  # 4 coincident bodies
    eps = 1e10
    expected = pairwise_accelerations_dense(pos, masses, eps=eps)
    got = pallas_pairwise_accelerations(
        pos, masses, eps=eps, tile_i=32, tile_j=128, interpret=True
    )
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-5, atol=1e-12
    )


def test_padding_is_exact(key):
    """Results are identical whether N is tile-aligned or ragged."""
    pos, masses = _random_system(key, 200)
    ragged = pallas_pairwise_accelerations(
        pos, masses, tile_i=32, tile_j=128, interpret=True
    )
    expected = pairwise_accelerations_dense(pos, masses)
    np.testing.assert_allclose(
        np.asarray(ragged), np.asarray(expected), rtol=2e-5, atol=1e-12
    )


@pytest.mark.parametrize("eps", [0.0, 1e10], ids=["masked", "mask_free"])
@pytest.mark.parametrize(
    "n, tile", [(100, 128), (512, 128), (1000, 128), (1000, 256)],
    ids=["one_tile", "four_tiles", "ragged", "ragged_two_chunks"],
)
def test_symmetric_matches_dense_and_rectangular(key, n, tile, eps):
    """The symmetric all-pairs kernel (square tiles of 128, one lane
    chunk of 16 blocks, or of 256, two chunks of 32) == the dense sum ==
    the rectangular kernel, on both the masked path (eps = 0: two
    coincident bodies in different tiles give each other zero, no NaN)
    and the mask-free one (eps > cutoff)."""
    assert symmetric_tile(n, 32, tile) == tile
    pos, masses = _random_system(key, n)
    pos = pos.at[n - 1].set(pos[0])  # coincident, a tile apart for n > tile
    got = pallas_pairwise_accelerations(
        pos, masses, eps=eps, tile_i=32, tile_j=tile, interpret=True
    )
    rect = pallas_accelerations_vs(
        pos, pos, masses, eps=eps, tile_i=32, tile_j=128, interpret=True
    )
    expected = pairwise_accelerations_dense(pos, masses, eps=eps)
    assert bool(jnp.all(jnp.isfinite(got)))
    for want in (expected, rect):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-12
        )


def _jaxpr_eqns(jaxpr):
    """Every equation of ``jaxpr``, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


def test_symmetric_kernel_values_fit_the_register_file():
    """The symmetric kernel runs its pair pipeline on vreg-sized blocks:
    traced at the default tile (2048 for N = 4,096), no value in the
    kernel's jaxpr, loop bodies included, has more than 65,536 elements,
    64 vregs of (8, 128) f32, the v5e's register file. A (T, T) value of
    the pipeline would have 4,194,304."""
    pos, masses = _random_system(jax.random.PRNGKey(3), 4096)
    assert symmetric_tile(4096) == 2048
    closed = jax.make_jaxpr(
        lambda p, m: pallas_pairwise_accelerations(p, m, eps=1e9)
    )(pos, masses)
    kernels = [eqn for eqn in _jaxpr_eqns(closed.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    eqns = list(_jaxpr_eqns(kernels[0].params["jaxpr"]))
    assert any(eqn.primitive.name == "rsqrt" for eqn in eqns)  # in view
    sizes = [var.aval.size for eqn in eqns for var in eqn.outvars
             if isinstance(var.aval, jax.core.ShapedArray)]
    assert max(sizes) <= 64 * 8 * 128


@pytest.mark.parametrize("form", ["symmetric", "rectangular"])
def test_total_momentum_at_fp32_rounding(form):
    """Σ m_i a_i vanishes to fp32 rounding of the sums, |Σ m_i a_i| <
    1e-7 Σ |m_i a_i|, for unequal masses: the column sums carry each
    pair's reaction -w_ji·d with the mass of the other body. Both kernels
    land at ~1e-9; neither is reliably the closer (each pair's two terms
    are as antisymmetric in one kernel as in the other: only the order
    of the sums differs)."""
    pos, masses = _random_system(jax.random.PRNGKey(7), 640)
    if form == "symmetric":
        acc = pallas_pairwise_accelerations(
            pos, masses, tile_i=32, tile_j=128, interpret=True
        )
    else:
        acc = pallas_accelerations_vs(
            pos, pos, masses, tile_i=32, tile_j=128, interpret=True
        )
    p = np.asarray(masses, np.float64)[:, None] * np.asarray(acc, np.float64)
    assert np.linalg.norm(p.sum(axis=0)) < 1e-7 * np.abs(p).sum()


def test_form_chosen_by_n(key, monkeypatch):
    """N alone picks the form: the benchmark's 262,144 bodies take the
    symmetric kernel on 2048 tiles (127 of 129 tiles per tile-row pair
    serve both bodies); above the VMEM bound the entry is the
    rectangular kernel, and pair_symmetric_share reads 0."""
    assert symmetric_tile(262_144) == 2048
    assert pair_symmetric_share(262_144) == pytest.approx(127 / 129)
    assert symmetric_tile(SYM_MAX_N) is not None
    assert symmetric_tile(SYM_MAX_N + 1) is None
    assert pair_symmetric_share(SYM_MAX_N + 1) == 0.0
    # The fallback, at a size the interpreter can run: the rectangular
    # kernel's output, bit for bit.
    n = 333
    monkeypatch.setattr(pallas_forces, "SYM_MAX_N", n - 1)
    assert pair_symmetric_share(n) == 0.0
    pos, masses = _random_system(key, n)
    got = pallas_pairwise_accelerations(
        pos, masses, tile_i=32, tile_j=128, interpret=True
    )
    rect = pallas_accelerations_vs(
        pos, pos, masses, tile_i=32, tile_j=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rect))


def test_run_stats_report_pair_symmetric_share():
    """The solo Pallas run reports the share of its pair tiles that
    served both bodies; the ring, whose hops all run the rectangular
    kernel, reports 0."""
    from gravity_tpu.config import SimulationConfig
    from gravity_tpu.simulation import Simulator

    base = dict(model="random", n=256, steps=2, dt=3600.0, seed=1,
                force_backend="pallas", integrator="leapfrog")
    solo = Simulator(SimulationConfig(**base)).run()
    assert solo["pair_symmetric_share"] == pair_symmetric_share(256) == 1 / 3
    ring = Simulator(SimulationConfig(**base, sharding="ring"))
    assert ring.mesh is not None
    assert ring.run()["pair_symmetric_share"] == 0.0
