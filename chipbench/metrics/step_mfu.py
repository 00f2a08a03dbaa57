"""The whole step's share of the chips' peak, over the traced window.

The algorithmic flops of the window's force evaluations
(``forces_roofline``'s count, from the configuration's work module)
over the traced window's length on the profiler's clock (the harness's
spans from the start of ``Simulator.run`` to the end of its fence), the
chips used and the published peak (``peaks.json``: the v5e publishes
only its bfloat16 peak, so fp32 work reads low against it).

It is ``step_s`` put as a share of the chip: by design, since it bounds
``forces_roofline`` from below whatever kernel runs, and stays readable
when a later change takes that kernel off the path, so a kernel's gain
can be claimed only while the whole step's share moves with it.
"""

LAYER = "whole step"
UNIT = "%"
BETTER = "higher"
MOVES = "step_s"
SOURCE = "device_trace"


def read(f):
    if f.trace is None or f.trace.window_ns <= 0 or f.flops is None:
        return None
    window_s = f.trace.window_ns / 1e9
    return 100.0 * f.flops / (window_s * f.chips * f.peaks["peak_flops"])
