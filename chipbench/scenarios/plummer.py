"""Plummer-sphere initial conditions, made on the device from ``--seed``.

A copy of ``gravity_tpu/models/plummer.py::create_plummer`` (Plummer
1911, sampled as Aarseth, Henon & Wielen 1974), kept here so that a
change to the program cannot move the benchmark's inputs. It keeps the
program's ``expm1`` fix: the TPU's fp32 ``pow`` rounds ``X**(-2/3) - 1``
to exactly 0 for X within a few ulp of 1 (r = inf, and the centering
then turns every position NaN).

Reads ``total_mass`` and ``scale_radius`` from the configuration, and
``n`` and ``g`` from its ``simulation``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def make(key, cfg: dict):
    sim = cfg["simulation"]
    return plummer(key, n=sim["n"], total_mass=cfg["total_mass"],
                   scale_radius=cfg["scale_radius"], g=sim["g"])


@partial(jax.jit, static_argnames=("n", "total_mass", "scale_radius", "g"))
def plummer(key, *, n: int, total_mass: float, scale_radius: float,
            g: float):
    """(positions (n, 3), velocities (n, 3), masses (n,)) in float32."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    f = jnp.float32
    x = jax.random.uniform(k1, (n,), dtype=f, minval=1e-8, maxval=1.0 - 1e-8)
    r = scale_radius / jnp.sqrt(jnp.expm1(-(2.0 / 3.0) * jnp.log(x)))

    costh = jax.random.uniform(k2, (n,), dtype=f, minval=-1.0, maxval=1.0)
    sinth = jnp.sqrt(jnp.maximum(0.0, 1.0 - costh * costh))
    phi = jax.random.uniform(k3, (n,), dtype=f, minval=0.0,
                             maxval=2.0 * jnp.pi)
    positions = r[:, None] * jnp.stack(
        [sinth * jnp.cos(phi), sinth * jnp.sin(phi), costh], axis=1)

    # Speed by von Neumann rejection on q = v/v_esc with
    # g(q) = q^2 (1 - q^2)^(7/2), as a fixed 8-round accept-resample.
    def body(carry, k):
        q, ok = carry
        ka, kb = jax.random.split(k)
        q_new = jax.random.uniform(ka, (n,), dtype=f)
        y = jax.random.uniform(kb, (n,), dtype=f, maxval=0.1)
        accept = y < q_new**2 * (1.0 - q_new**2) ** 3.5
        take = jnp.logical_and(accept, jnp.logical_not(ok))
        return (jnp.where(take, q_new, q), jnp.logical_or(ok, accept)), None

    (q, _), _ = jax.lax.scan(
        body, (jnp.full((n,), 0.5, f), jnp.zeros(n, bool)),
        jax.random.split(k4, 8))
    v_esc = jnp.sqrt(2.0 * g * total_mass) * (
        r * r + scale_radius * scale_radius) ** (-0.25)
    speed = q * v_esc
    costh_v = jax.random.uniform(k5, (n,), dtype=f, minval=-1.0, maxval=1.0)
    sinth_v = jnp.sqrt(jnp.maximum(0.0, 1.0 - costh_v * costh_v))
    phi_v = jax.random.uniform(jax.random.fold_in(k5, 1), (n,), dtype=f,
                               minval=0.0, maxval=2.0 * jnp.pi)
    velocities = speed[:, None] * jnp.stack(
        [sinth_v * jnp.cos(phi_v), sinth_v * jnp.sin(phi_v), costh_v], axis=1)

    masses = jnp.full((n,), total_mass / n, dtype=f)
    positions = positions - jnp.mean(positions, axis=0, keepdims=True)
    velocities = velocities - jnp.mean(velocities, axis=0, keepdims=True)
    return positions, velocities, masses
