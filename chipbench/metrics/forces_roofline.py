"""The force kernel's share of its roofline, from the device trace.

Numerator: the least time the chip could take for the work the
ALGORITHM needs, the same whatever kernel does it: the flops and bytes
of the window's force evaluations as the configuration's work module
counts them (``work/<name>.py``: the all-pairs sum's 20 flops per
directed pair over N(N-1) pairs, or the truncated law's over the pairs
within its radius), at the chip's published peak (``peaks.json``; the
v5e publishes only its bfloat16 matrix peak, so an fp32 vector kernel
cannot come near 100%) and its memory bandwidth: both bounds are
computed and the larger taken (the result line's ``window.work.bound``
says which).

Denominator: the device time of the force-kernel ops in the window, the
mean over the chips used, times the number of chips.
"""

from chipbench.trace import FORCE_KERNEL as KERNEL

LAYER = "force kernel"
UNIT = "%"
BETTER = "higher"
MOVES = "step_s"
SOURCE = "device_trace"


def read(f):
    from chipbench.trace import length

    if f.trace is None or not f.trace.devices or f.flops is None:
        return None
    times = [length(f.trace.intervals(d, KERNEL))
             for d in range(len(f.trace.devices))]
    if min(times) <= 0:
        return None
    kernel_s = sum(times) / len(times) / 1e9
    bound_s = max(f.flops / f.peaks["peak_flops"],
                  f.bytes / f.peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (f.chips * kernel_s)
