"""Device-idle time charged to the host span that covers it.

A profiler trace of a run holds the program's own spans (``gravity.*``,
opened by ``gravity_tpu/telemetry/tracing.span`` around each phase of
``Simulator.run``) on the same clock as the device's ops and the
harness's spans (``chipbench.*``). Each nanosecond in which a device ran
no op inside the harness's window is charged to the innermost span that
covers it: of the spans open at that moment, the one opened last (the
shorter on a tie). Idle time that no span covers is charged to ``None``.
So the charges of one device sum to its idle time in the window, and the
mean over devices of all of them is the idle of ``device.idle_share``.

``DeviceTrace`` keeps both: ``host_spans`` (the harness's) and
``program_spans`` (the program's).
"""

from __future__ import annotations

from chipbench.trace import PROGRAM, minus

# The driver's phases that open and close every Simulator.run.
OPEN = ("gravity.run.open",)
CLOSE = ("gravity.run.drain", "gravity.run.close", "gravity.run.final_state")
# The host's work on a finished block's outputs, and the checkpoint
# saves it hands to the background writer.
CONSUME = ("gravity.block.consume", "gravity.checkpoint")


def innermost(spans) -> list:
    """``[start, end, name]`` pieces of the time line, sorted and
    disjoint, each with the innermost of ``spans`` open all through it;
    time with no span open is left out."""
    marks = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out, live, k = [], [], 0
    for a, b in zip(marks, marks[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            live.append(by_start[k])
            k += 1
        live = [sp for sp in live if sp[2] > a]
        if live:
            inner = max(live, key=lambda sp: (sp[1], -sp[2]))
            out.append([a, b, inner[0]])
    return out


def charge(idle, spans) -> dict:
    """``{span name or None: ns}``: each of the sorted, disjoint
    ``idle`` intervals cut by the innermost span over each of its
    parts."""
    pieces = innermost(spans)
    out: dict = {}
    j = 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            part = min(b, e) - max(a, s)
            out[name] = out.get(name, 0.0) + part
            covered += part
            k += 1
        if e - s > covered:
            out[None] = out.get(None, 0.0) + (e - s - covered)
    return out


def idle_charges(dtrace, spans) -> list:
    """Per device of a ``DeviceTrace``, its idle time in the window
    charged by :func:`charge`."""
    lo, hi = dtrace.window
    return [charge(minus([[lo, hi]], dtrace.intervals(d)), spans)
            for d in range(len(dtrace.devices))]


def shares(dtrace, spans) -> dict:
    """``{span name or None: % of the window}``, the mean over devices;
    the values sum to the window's mean idle share."""
    per_dev = idle_charges(dtrace, spans)
    if not per_dev or dtrace.window_ns <= 0:
        return {}
    out: dict = {}
    for charges in per_dev:
        for name, ns in charges.items():
            out[name] = out.get(name, 0.0) + ns
    return {name: 100.0 * ns / len(per_dev) / dtrace.window_ns
            for name, ns in out.items()}


def share(dtrace, spans, names) -> float | None:
    """The % of the window in which the devices sat idle inside one of
    the program spans ``names`` (innermost among ``spans``), the mean
    over devices; None where ``spans`` holds no program span or the
    trace no device."""
    if not any(sp[0].startswith(PROGRAM) for sp in spans):
        return None
    if not dtrace.devices or dtrace.window_ns <= 0:
        return None
    got = shares(dtrace, spans)
    return sum(got.get(n, 0.0) for n in names)
