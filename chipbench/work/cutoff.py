"""The work of one force evaluation of the truncated pair law.

A force evaluation under a truncation radius ``rcut`` (the simulation's
``nlist_rcut``) needs the pairs closer than it and no others: 20 flops
per directed pair within ``rcut`` (``direct.py``'s count per pair), and
28 B per body. The pairs are counted exactly on the host, in float64,
with a k-d tree over the initial conditions: the count is taken at the
window's start, and the cube's slow expansion over a window changes it
by little. A pair at exactly ``rcut`` counts, as the truncation keeps
it (r <= rcut).
"""

import os

import numpy as np

FLOPS_PER_PAIR = 20
BYTES_PER_BODY = 16.0 + 12.0


def pairs_within(x, rcut: float) -> int:
    """Ordered pairs (i, j), i != j, with |x_i - x_j| <= rcut."""
    from scipy.spatial import cKDTree

    x = np.asarray(x, np.float64)
    counts = cKDTree(x).query_ball_point(
        x, r=rcut, return_length=True,
        workers=len(os.sched_getaffinity(0)) or 1)
    return int(counts.sum()) - len(x)


def per_eval(cfg: dict, x0, masses) -> dict:
    rcut = cfg["simulation"]["nlist_rcut"]
    pairs = pairs_within(x0, rcut)
    return {"flops": float(FLOPS_PER_PAIR) * pairs,
            "bytes": BYTES_PER_BODY * len(masses),
            "bound_note": f"20 flop per directed pair within rcut = "
                          f"{rcut:g} m, {pairs} pairs at the window's "
                          "start; 28 B per body"}
