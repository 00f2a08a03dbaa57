"""Device idle while the host consumes finished blocks' outputs.

Each idle nanosecond of a device inside the window is charged to the
innermost span over it, the program's (``gravity.*``) or the harness's
(``chipbench.*``), as ``spans.py`` does; this is the part charged to
``spans.CONSUME``: ``gravity.block.consume`` (from a block's fence to
the next dispatch: the ledger's read, the trajectory's D2H, the
checkpoint's submit) and ``gravity.checkpoint`` (one save, on the
thread that runs it), as % of the window, the mean over the chips used.
None on a trace with no program spans.
"""

from chipbench import spans

LAYER = "driver block loop"
UNIT = "%"
BETTER = "lower"
MOVES = "step_s"
SOURCE = "device_trace"


def read(f):
    if f.trace is None:
        return None
    return spans.share(f.trace, f.trace.host_spans + f.trace.program_spans,
                       spans.CONSUME)
