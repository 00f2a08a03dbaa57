"""Compile the main-path Pallas kernels for a described TPU v5e, no chip.

The CPU suite runs every kernel in interpret mode, which cannot see
what Mosaic refuses (the nlist kernel's vector SMEM load and its erf
never compiled for the chip until PR 21). Each test lowers a kernel
builder with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology at the size the smoke phases run it, compiles it
with the TPU compiler installed here, and checks the Mosaic kernel is
in the program. All of these live in this one file: the topology is
described only inside the module fixture below, so only the worker
that runs this file loads the TPU library.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gravity_tpu.ops.pallas_forces import SYM_MAX_N

N_FLAGSHIP = 262_144  # chip_smoke.py's flagship and bench.py's size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile is written to a persistent cache but
    # cannot be read back without a chip: keep it out of any cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_vpu_direct_sum_compiles(one_chip):
    from gravity_tpu.ops.pallas_forces import pallas_accelerations_vs

    n = N_FLAGSHIP
    text = _compiled_text(
        partial(pallas_accelerations_vs, eps=1e9, interpret=False),
        one_chip, (n, 3), (n, 3), (n,),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [N_FLAGSHIP, SYM_MAX_N])
def test_symmetric_direct_sum_compiles(one_chip, n):
    """The all-pairs entry's symmetric kernel at the flagship size and at
    the largest N it takes, where its column accumulator fills the VMEM
    it claims, both on the default 2048 tile."""
    from gravity_tpu.ops.pallas_forces import (
        pallas_pairwise_accelerations,
        symmetric_tile,
    )

    assert symmetric_tile(n) == 2048
    text = _compiled_text(
        partial(pallas_pairwise_accelerations, eps=1e9, interpret=False),
        one_chip, (n, 3), (n,),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_mxu_direct_sum_compiles(one_chip, precision):
    from gravity_tpu.ops.pallas_forces_mxu import pallas_accelerations_vs_mxu

    n = N_FLAGSHIP
    text = _compiled_text(
        partial(pallas_accelerations_vs_mxu, eps=1e9, precision=precision,
                interpret=False),
        one_chip, (n, 3), (n, 3), (n,),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["newton", "ewald"])
def test_nlist_tile_kernel_compiles(one_chip, kind):
    """The cell-list pair-tile kernel at side 32 / cap 64 (2M slots):
    ``newton`` is the standalone cutoff backend, ``ewald`` the P3M near
    field through the in-kernel erfc."""
    from gravity_tpu.ops.pallas_nlist import _pallas_pair_cells

    side, cap = 32, 64
    cells = side**3

    def tiles(t, c, gm, params):
        return _pallas_pair_cells(
            t, c, gm, side, params, kind=kind, cutoff=1e-10, eps=1e9,
            use_rcut=True, interpret=False,
        )

    text = _compiled_text(
        tiles, one_chip, (cells, cap, 3), (cells, cap, 3), (cells, cap),
        (2,),
    )
    assert "tpu_custom_call" in text
