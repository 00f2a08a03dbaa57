"""The work of one force evaluation of the exact all-pairs sum.

20 flops per directed pair (Nyland, Harris & Prins, GPU Gems 3 ch. 31:
3 subtractions, 6 for r^2 and the softening, 1 rsqrt, 4 for the weight,
6 to accumulate), over all N(N-1) directed pairs; the least bytes are
16 B per body read (position and mass) and 12 B per body written (its
acceleration). The count is the algorithm's, the same whatever kernel
does it.
"""

FLOPS_PER_PAIR = 20
BYTES_PER_BODY = 16.0 + 12.0


def per_eval(cfg: dict, x0, masses) -> dict:
    n = len(masses)
    return {"flops": float(FLOPS_PER_PAIR) * n * (n - 1),
            "bytes": BYTES_PER_BODY * n,
            "bound_note": "20 flop per directed pair, N(N-1) pairs; "
                          "28 B per body"}
