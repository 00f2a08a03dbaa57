"""Initial conditions from ``--seed``, by the scenario the configuration names.

A configuration's ``"ics"`` names a module ``scenarios/<ics>.py`` with
``make(key, cfg) -> (positions (n, 3), velocities (n, 3), masses (n,))``,
made on the device in float32 from the key; it reads its parameters
from the configuration (``cfg["simulation"]`` holds ``n`` as the run
uses it).

The seed is folded into the key 32 bits at a time, so any whole number
the driver passes (it goes past 2**31) gives its own realization.
"""

from __future__ import annotations

import jax

from chipbench import registry


def seed_key(seed: int) -> jax.Array:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0; got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key


def make(cfg: dict, seed: int, *, base: str = registry.HERE):
    """The initial conditions of ``cfg`` for ``seed``."""
    return registry.module("scenarios", cfg["ics"], base=base).make(
        seed_key(seed), cfg)
