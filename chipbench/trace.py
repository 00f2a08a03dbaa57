"""The shared reader of the profiler's trace (``*.xplane.pb``).

It turns a trace into plain data (planes -> lines -> events of
``[name, start_ns, dur_ns, stats]``), so that the reductions below run
the same on a trace the chip wrote and on the small recorded fixture in
``tests/chipbench``. Busy time is the union of the intervals of the
device's ops, clipped to the window the harness annotated; which ops
count as what is for each metric module to say, by the patterns it
declares.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_START = "chipbench.window.run"
WINDOW_END = "chipbench.window.fence"
HARNESS = "chipbench."
PROGRAM = "gravity."
# The force kernels' ops: the Pallas kernels (ops/pallas_forces.py
# _nbody_kernel, ops/pallas_forces_mxu.py, ops/pallas_nlist.py) run as
# Mosaic custom calls.
FORCE_KERNEL = (r"_nbody_kernel", r"nbody", r"pallas", r"tpu_custom_call",
                r"^custom-call")
# Device planes as the TPU runtime names them, and the line of one
# event per executed HLO op on each.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def newest_xplane(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns),
                 {str(k): v for k, v in dict(ev.stats).items()
                  if isinstance(v, (str, int, float))}]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def host_events(trace: dict, prefix: str) -> list:
    """``[name, start, end]`` of the host events of a loaded trace whose
    names start with ``prefix``."""
    return [[name, s, s + d]
            for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for line in p["lines"] for name, s, d, _ in line["events"]
            if name.startswith(prefix)]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list:
    """Union ``a`` with union ``b`` cut out (both merged and sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def matches(event, patterns) -> bool:
    """Whether any pattern finds the op's name, or any of its string
    stats (``long_name``, ``hlo_op``, ``tf_op``, ...)."""
    name, _, _, stats = event
    texts = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(re.search(p, t) for p in patterns for t in texts)


class DeviceTrace:
    """The device ops of a trace inside the harness's annotated window,
    with the host spans of the harness (``host_spans``) and of the
    program (``program_spans``), each ``[name, start, end]``."""

    def __init__(self, trace: dict):
        self.window = _window(trace)
        lo, hi = self.window
        self.devices = []  # one list of clipped op events per device
        for plane in sorted(
                (p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1))):
            evs = []
            for line in plane["lines"]:
                if line["name"] != OPS_LINE:
                    continue
                for name, s, d, stats in line["events"]:
                    e = min(s + d, hi)
                    s = max(s, lo)
                    if e > s:
                        evs.append([name, s, e - s, stats])
            self.devices.append(evs)
        self.host_spans = host_events(trace, HARNESS)
        self.program_spans = host_events(trace, PROGRAM)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def intervals(self, dev: int, patterns=None) -> list:
        return union([s, s + d] for s, d in (
            (ev[1], ev[2]) for ev in self.devices[dev]
            if patterns is None or matches(ev, patterns)))

    def busy_ns(self, dev: int) -> float:
        return length(self.intervals(dev))

    def mean_busy_s(self) -> float:
        n = len(self.devices)
        return sum(self.busy_ns(d) for d in range(n)) / n / 1e9 if n else 0.0

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (mean over devices), and
        the longest idle gaps of device 0, each named by the innermost
        span, the harness's or the program's, over the gap's middle: of
        those open then, the one opened last (the shorter on a tie), as
        ``spans.innermost`` charges idle time."""
        n = max(len(self.devices), 1)
        per_op: dict = {}
        for evs in self.devices:
            for name, _, d, _ in evs:
                # The TPU names an op by its HLO text: keep "%name.N".
                name = name.split(" = ", 1)[0]
                per_op[name] = per_op.get(name, 0.0) + d / n
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.devices:
            lo, hi = self.window
            idle = minus([[lo, hi]], self.intervals(0))
            spans = self.host_spans + self.program_spans
            for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
                mid = 0.5 * (s + e)
                inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
                label = (max(inner, key=lambda sp: (sp[1], -sp[2]))[0]
                         if inner else "no span")
                gaps.append([f"idle in {label}", (e - s) / 1e9])
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": gaps}


def _window(trace: dict) -> tuple:
    starts = [s for p in trace["planes"] for line in p["lines"]
              for name, s, d, _ in line["events"] if name == WINDOW_START]
    ends = [s + d for p in trace["planes"] for line in p["lines"]
            for name, s, d, _ in line["events"] if name == WINDOW_END]
    if not starts or not ends:
        raise ValueError(
            f"trace has no {WINDOW_START!r} / {WINDOW_END!r} span")
    return min(starts), max(ends)
