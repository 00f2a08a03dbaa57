"""Uniform random-cube initial conditions, made on the device from ``--seed``.

The upstream's random filler (``cuda.cu:121-138``, ``mpi.c:96-107``):
positions uniform in [-pos_bound, pos_bound]^3, velocities uniform in
[-vel_bound, vel_bound]^3, masses uniform in [mass_low, mass_high],
each drawn independently, in float32. The upstream's three-body solar
seed is left out (the configuration's ``assumed`` says why).

Reads ``pos_bound``, ``vel_bound``, ``mass_low`` and ``mass_high`` from
the configuration, and ``n`` from its ``simulation``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def make(key, cfg: dict):
    return cube(key, n=cfg["simulation"]["n"], pos_bound=cfg["pos_bound"],
                vel_bound=cfg["vel_bound"], mass_low=cfg["mass_low"],
                mass_high=cfg["mass_high"])


@partial(jax.jit, static_argnames=("n", "pos_bound", "vel_bound",
                                   "mass_low", "mass_high"))
def cube(key, *, n: int, pos_bound: float, vel_bound: float,
         mass_low: float, mass_high: float):
    """(positions (n, 3), velocities (n, 3), masses (n,)) in float32."""
    kp, kv, km = jax.random.split(key, 3)
    f = jnp.float32
    positions = jax.random.uniform(kp, (n, 3), f, -pos_bound, pos_bound)
    velocities = jax.random.uniform(kv, (n, 3), f, -vel_bound, vel_bound)
    masses = jax.random.uniform(km, (n,), f, mass_low, mass_high)
    return positions, velocities, masses
