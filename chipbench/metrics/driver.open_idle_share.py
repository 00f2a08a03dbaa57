"""Device idle while ``Simulator.run`` opens: the opening force evaluation.

Each idle nanosecond of a device inside the window is charged to the
innermost span over it, the program's (``gravity.*``) or the harness's
(``chipbench.*``), as ``spans.py`` does; this is the part charged to
``spans.OPEN``: ``gravity.run.open``, which holds the opening force
evaluation (on the ring its ``shard_map`` retraces), the ledger's
baseline and the donation snapshot, as % of the window, the mean over
the chips used. None on a trace with no program spans.
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
                       spans.OPEN)
