"""The share of the window the device spends busy outside the force kernel.

Per device: the union of its op intervals with the force kernel's ops
(``trace.FORCE_KERNEL``, those ``forces_roofline`` times) cut out, over
the window; the mean over the chips used. It is the rest of the step
on the device: the integrator's updates, the cell list's binning sort
and scatter and its remainder channel, the ring's exposed exchange.
None where no force-kernel op is found.
"""

from chipbench.trace import FORCE_KERNEL, length, minus

LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "step_s"
SOURCE = "device_trace"


def read(f):
    tr = f.trace
    if tr is None or not tr.devices or tr.window_ns <= 0:
        return None
    shares = []
    for d in range(len(tr.devices)):
        kernel = tr.intervals(d, FORCE_KERNEL)
        if not kernel:
            return None
        shares.append(length(minus(tr.intervals(d), kernel)) / tr.window_ns)
    return 100.0 * sum(shares) / len(shares)
