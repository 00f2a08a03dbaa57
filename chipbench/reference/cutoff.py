"""The plain reference for one window of the truncated pair law.

What the window produced is the final state of ``Simulator.run`` after
``steps`` leapfrog (kick-drift-kick) steps of ``dt`` under forces
truncated at ``rcut`` (the simulation's ``nlist_rcut``): each body is
pulled by the softened Newtonian force of every other body within
``rcut`` of it (r <= rcut, unsoftened distance) and by nothing beyond.
The reference follows a sample of target bodies, drawn from the seed
(``window.sample_targets``), through the same steps; ``window.compare``
reads the gaps. It takes nothing the program made: its only inputs are
the benchmark's initial conditions and the configuration.

The reference:

- integrates an active set: the sampled targets and every body whose
  path comes within ``reach`` of a target's during the window. Each
  active body keeps its position and velocity in float32, the storage
  precision the configuration states, with the leapfrog's three updates
  done one rounding at a time in numpy, as ``window.py`` does them;
- takes every acceleration of an active body as a float64 sum over the
  sources within ``rcut`` of it at that step, the self pair left out by
  index (softened Plummer kernel, G m_j folded in before the reciprocal
  powers, pairs under the ``cutoff`` dropped, as ``window.py``). Active
  sources sit where the reference has integrated them; every other
  source moves on its straight line ``x0 + v0 t``;
- finds, once, for each active body the sources whose straight path
  comes within ``reach`` of its own at some time of the window, and
  sums over those alone: a source outside that list is never within
  ``rcut`` of the body;
- ``reach`` = rcut + ``margin``, with ``margin`` = DEVIATION_PULLS x
  a_pair x T^2: two bodies leave their straight lines by at most
  a T^2 / 2 each, and a_pair = 2 G m_max / (3 sqrt(3) eps^2) is the
  strongest pull one source can give under the softening. A body that
  is not active moves by its initial velocity alone; what that costs
  the targets was measured against a second shell made active
  (``PERF.md`` §4).

Everything runs on the host's CPU devices, after the window has closed;
it imports nothing of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.window import PAIR_DTYPES

DEVIATION_PULLS = 8.0


def reach_of(m, *, g: float, eps: float, rcut: float,
             window_s: float) -> float:
    if eps <= 0.0:
        raise ValueError("the cutoff reference needs a softening eps > 0")
    a_pair = 2.0 * g * float(np.max(m)) / (3.0 * np.sqrt(3.0) * eps * eps)
    return rcut + DEVIATION_PULLS * a_pair * window_s * window_s


@partial(jax.jit, static_argnames=("run", "width", "reach", "window_s",
                                   "half"))
def _kept(xs, vs, xa, va, rank, first, last, *, run: int, width: int,
          reach: float, window_s: float, half: int):
    """For bodies at ``xa``, ``va`` (their places ``rank`` among the
    sorted bodies ``xs, vs``), the places of the candidates that come
    within ``reach`` of them, with the closest approach of the straight
    paths in the given half of the window: (rows, width) packed to the
    left and padded with -1, and the count of each row. A body's
    candidates are, for each of its 25 runs, the sorted bodies from
    ``first`` up to ``last`` (at most ``run`` of them)."""
    at = (first[..., None] + jnp.arange(run)).reshape(xa.shape[0], -1)
    real = (at < jnp.repeat(last, run, axis=1)) & (at != rank[:, None])
    at = jnp.minimum(at, xs.shape[0] - 1)
    d0 = xs[at] - xa[:, None]
    dv = vs[at] - va[:, None]
    dv2 = (dv * dv).sum(-1)
    t = jnp.clip(-(d0 * dv).sum(-1) / jnp.maximum(dv2, 1e-300), 0.0,
                 window_s)
    d = d0 + dv * t[..., None]
    late = t >= 0.5 * window_s
    mine = late if half else jnp.logical_not(late)
    keep = real & mine & ((d * d).sum(-1) <= reach * reach)
    slot = jnp.where(keep, jnp.cumsum(keep, axis=1) - 1, width)
    rows = jnp.broadcast_to(jnp.arange(keep.shape[0])[:, None], keep.shape)
    out = jnp.full((keep.shape[0], width), -1, at.dtype)
    return out.at[rows, slot].set(at, mode="drop"), keep.sum(1)


def _grid(p, edge: float):
    """Bodies sorted by the cell of a grid of ``edge`` that holds them,
    x fastest: (cell of each body (n, 3), cells per axis, the order, and
    where each cell starts in it)."""
    lo = p.min(0)
    side = np.maximum(1, np.ceil((p.max(0) - lo) / edge).astype(np.int64))
    c = np.minimum(((p - lo) / edge).astype(np.int64), side - 1)
    key = (c[:, 1] * side[2] + c[:, 2]) * side[0] + c[:, 0]
    order = np.argsort(key, kind="stable")
    return c, side, order, np.searchsorted(key[order],
                                           np.arange(side.prod() + 1))


def near_paths(x0, v0, rows, *, reach: float, window_s: float,
               chunk: int = 512) -> np.ndarray:
    """For each body of ``rows``, the indices of the other bodies whose
    straight path comes within ``reach`` of its own in the window, as a
    (rows, most found) array padded with -1.

    Each half of the window is searched at its middle: a pair whose
    closest approach falls in a half lies within ``bound`` = reach +
    2 max|v| T/4 of each other at that half's middle, so within two
    cells of a grid of edge bound/2. The bodies are sorted by cell, x
    fastest, so the five cells along x of each (y, z) column are one
    contiguous run: 25 runs hold every candidate. Each pair is kept
    from the half that holds its closest approach alone. The chunks of
    rows go to the CPU devices in turn."""
    rows = np.asarray(rows)
    vmax = float(np.sqrt((v0 * v0).sum(-1)).max())
    bound = reach + 2.0 * vmax * window_s / 4
    offs = np.array([(dy, dz) for dy in range(-2, 3) for dz in range(-2, 3)])
    devices = jax.devices("cpu")
    got = [[] for _ in range(0, len(rows), chunk)]
    with jax.enable_x64(True):
        put = lambda a, d: jax.device_put(np.asarray(a), d)
        for half in (0, 1):
            c, side, order, start = _grid(
                x0 + v0 * ((half + 0.5) * window_s / 2), bound / 2)
            ca = c[rows]
            y = ca[:, None, 1] + offs[None, :, 0]
            z = ca[:, None, 2] + offs[None, :, 1]
            inside = (y >= 0) & (y < side[1]) & (z >= 0) & (z < side[2])
            col = (np.clip(y, 0, side[1] - 1) * side[2]
                   + np.clip(z, 0, side[2] - 1)) * side[0]
            first = start[col + np.maximum(ca[:, None, 0] - 2, 0)]
            last = start[col + np.minimum(ca[:, None, 0] + 2, side[0] - 1)
                         + 1]
            last = np.where(inside, last, first)
            run = max(1, int((last - first).max(initial=0)))
            rank = np.empty(len(order), np.int64)
            rank[order] = np.arange(len(order))
            xv = [(put(x0[order], d), put(v0[order], d)) for d in devices]
            width = 1024
            for w in range(0, len(got), len(devices)):
                wave = []
                for i, d in enumerate(devices[:len(got) - w]):
                    sl = slice((w + i) * chunk, (w + i + 1) * chunk)
                    r = rows[sl]
                    args = (*xv[i], put(x0[r], d), put(v0[r], d),
                            put(rank[r], d), put(first[sl], d),
                            put(last[sl], d))
                    wave.append((w + i, args, _kept(
                        *args, run=run, width=width, reach=reach,
                        window_s=window_s, half=half)))
                for k, args, (at, count) in wave:
                    while int(count.max()) > width:
                        width *= 2
                        at, count = _kept(*args, run=run, width=width,
                                          reach=reach, window_s=window_s,
                                          half=half)
                    at = np.asarray(at)[:, :int(count.max(initial=0))]
                    got[k].append(np.where(at >= 0, order[at], -1))
    pieces = []
    for part in got:
        both = np.concatenate(part, axis=1)
        keep = both >= 0
        r, c = np.nonzero(keep)
        out = np.full((len(both), int(keep.sum(1).max(initial=0))), -1,
                      np.int64)
        out[r, (np.cumsum(keep, axis=1) - 1)[r, c]] = both[r, c]
        pieces.append(out)
    out = np.full((len(rows), max(p.shape[1] for p in pieces)), -1,
                  np.int64)
    for k, p in enumerate(pieces):
        out[k * chunk:k * chunk + len(p), :p.shape[1]] = p
    return out


def active_set(x0, v0, idx, *, reach: float, window_s: float) -> np.ndarray:
    """Sorted indices of the targets ``idx`` and of every body whose
    straight path comes within ``reach`` of a target's."""
    near = near_paths(x0, v0, np.asarray(idx), reach=reach,
                      window_s=window_s)
    return np.unique(np.concatenate([np.asarray(idx), near[near >= 0]]))


def _make_accel(width: int, *, g: float, eps: float, cutoff: float,
                rcut: float, pair_dtype: str, devices, block: int):
    """``accel(act_x (D*kb, 3) f64, lists (D*kb, width), self (D*kb,), t,
    x0, v0, m, all_act_x (K, 3), all_act_idx (K,)) -> (D*kb, 3) f64``
    over the CPU devices of the mesh, each taking its ``kb`` actives
    ``block`` at a time against the sources of their lists."""
    from jax.sharding import Mesh, PartitionSpec as P

    low = pair_dtype == "bfloat16"
    ct = jnp.bfloat16 if low else jnp.float64
    acc_t = jnp.float32 if low else jnp.float64

    def one_device(tgt, lists, self_idx, t, x0, v0, m, act_x, act_idx):
        src = (x0 + t * v0).at[act_idx].set(act_x).astype(ct)
        gm = jnp.asarray(g, ct) * m.astype(ct)
        eps2 = jnp.asarray(eps, ct) * jnp.asarray(eps, ct)
        cut2 = jnp.asarray(cutoff, ct) * jnp.asarray(cutoff, ct)
        rcut2 = jnp.asarray(rcut, ct) * jnp.asarray(rcut, ct)

        def blk(args):
            tb, jb, si = args
            d = src[jb] - tb.astype(ct)[:, None, :]
            r2 = (d * d).sum(-1)
            ok = (r2 <= rcut2) & (r2 + eps2 > cut2) & (jb != si[:, None])
            inv_r = jax.lax.rsqrt(jnp.where(ok, r2 + eps2, jnp.ones((), ct)))
            w = jnp.where(ok, ((gm[jb] * inv_r) * inv_r) * inv_r,
                          jnp.zeros((), ct))
            return jnp.sum((w[..., None] * d).astype(acc_t), axis=1)

        kb = tgt.shape[0]
        out = jax.lax.map(blk, (tgt.reshape(kb // block, block, 3),
                                lists.reshape(kb // block, block, width),
                                self_idx.reshape(kb // block, block)))
        return out.reshape(kb, 3).astype(jnp.float64)

    mesh = Mesh(np.array(devices), ("d",))
    rep = P()
    return mesh, jax.jit(jax.shard_map(
        one_device, mesh=mesh, in_specs=(P("d"),) * 3 + (rep,) * 6,
        out_specs=P("d")))


def integrate_targets(x0, v0, m, idx, *, dt: float, steps: int, g: float,
                      eps: float, cutoff: float, nlist_rcut: float,
                      pair_dtype: str = "float64", block: int = 32):
    """Final (positions, velocities) of the targets ``idx``, float32,
    from the initial conditions ``x0, v0, m`` (N bodies each)."""
    if pair_dtype not in PAIR_DTYPES:
        raise ValueError(f"pair_dtype must be one of {PAIR_DTYPES}")
    n = len(m)
    window_s = dt * steps
    f64 = lambda a: np.asarray(a, np.float64)
    # Bodies in the order of a coarse grid, so that a body's sources
    # lie near it in memory.
    x0d, v0d, md = f64(x0), f64(v0), f64(m)
    lo, hi = x0d.min(0), x0d.max(0)
    cell = np.clip(((x0d - lo) / np.maximum(hi - lo, 1e-300) * 32)
                   .astype(np.int64), 0, 31)
    order = np.argsort((cell[:, 0] * 32 + cell[:, 1]) * 32 + cell[:, 2],
                       kind="stable")
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    x0d, v0d, md = x0d[order], v0d[order], md[order]
    tg = rank[np.asarray(idx)]

    reach = reach_of(md, g=g, eps=eps, rcut=nlist_rcut, window_s=window_s)
    act = active_set(x0d, v0d, tg, reach=reach, window_s=window_s)
    near = near_paths(x0d, v0d, act, reach=reach, window_s=window_s)
    k = len(act)
    # Empty slots point at the body itself: the self pair is dropped.
    width = max(8, -(-near.shape[1] // 8) * 8)
    lists = np.repeat(act[:, None], width, axis=1)
    lists[:, :near.shape[1]] = np.where(near >= 0, near, act[:, None])
    del near
    where = np.searchsorted(act, tg)

    devices = jax.devices("cpu")
    n_dev = min(len(devices), max(1, k // block))
    devices = devices[:n_dev]
    per = -(-k // (n_dev * block)) * block
    pad = per * n_dev - k
    act_p = np.concatenate([act, np.full(pad, act[-1])])
    lists = np.concatenate([lists, np.repeat(lists[-1:], pad, 0)])
    with jax.enable_x64(True):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, accel = _make_accel(width, g=g, eps=eps, cutoff=cutoff,
                                  rcut=nlist_rcut, pair_dtype=pair_dtype,
                                  devices=devices, block=block)
        rep = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
        split = NamedSharding(mesh, P("d"))
        src = (rep(x0d), rep(v0d), rep(md))
        act_rep = rep(act)
        lists_d = jax.device_put(lists.astype(np.int32), split)
        self_idx = jax.device_put(act_p.astype(np.int32), split)
        del lists

        def acc_at(x32, t):
            xa = f64(x32)
            tgt = np.concatenate([xa, np.repeat(xa[-1:], pad, 0)])
            a = accel(jax.device_put(tgt, split), lists_d, self_idx,
                      jnp.asarray(t, jnp.float64), *src, rep(xa), act_rep)
            return np.asarray(a)[:k].astype(np.float32)

        f32 = np.float32
        half, dt32 = f32(0.5 * dt), f32(dt)
        x = np.asarray(x0, f32)[order][act]
        v = np.asarray(v0, f32)[order][act]
        a = acc_at(x, 0.0)
        for s in range(steps):
            v_half = v + a * half
            x = x + v_half * dt32
            a = acc_at(x, (s + 1) * dt)
            v = v_half + a * half
    return x[where], v[where]
