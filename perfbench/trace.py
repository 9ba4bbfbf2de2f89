"""Reduce a profiler trace of the window to what the per-layer metrics
read: device busy and idle intervals, op time by category and by name,
program (XLA module) intervals, and the idle gaps between programs with
what the host was doing in each.

The trace is the ``.xplane.pb`` file that ``jax.profiler`` writes, read
with ``jax.profiler.ProfileData``. On a TPU each chip is a plane named
``/device:TPU:<n>`` with a line of XLA modules (one event per program
run, named ``jit_<function>(<program id>)``) and a line of XLA ops (one
event per op run, named by its HLO instruction's text); the host is the
plane ``/host:CPU``, one line per thread. Op categories ("convolution
fusion", "data formatting", ...) come from the TPU profiler's own
``hlo_stats`` table of the same file. The window is the host span named
``WINDOW_SPAN`` that the harness opens and closes; every device interval
is clipped to it.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "perfbench_window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"


class Event(NamedTuple):
    name: str
    start: float          # seconds, on the trace's clock
    end: float
    stats: dict


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9,
                         dict(e.stats)))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping intervals; sorted, disjoint."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float):
    """The idle intervals of [lo, hi] outside the disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(event_name: str) -> str:
    """``%fusion.149 = f32[...] fusion(...), ...`` -> ``fusion.149``: an
    XLA op event is named by its HLO instruction's text."""
    return event_name.split(" ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_chunk(1234)`` -> ``jit_chunk``."""
    return event_name.split("(", 1)[0]


def self_times(ops: List[Event], lo: float, hi: float):
    """(op name, self seconds in [lo, hi]) for each op event: its clipped
    duration less that of the events nested in it."""
    out = []
    stack: List[list] = []          # [end, name, clipped, children]

    def close(entry):
        out.append((entry[1], max(0.0, entry[2] - entry[3])))

    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and o.start >= stack[-1][0]:
            close(stack.pop())
        clipped = max(0.0, min(o.end, hi) - max(o.start, lo))
        if stack:
            stack[-1][3] += clipped
        stack.append([o.end, op_name(o.name), clipped, 0.0])
    while stack:
        close(stack.pop())
    return out


class Device(NamedTuple):
    name: str
    ops: List[Event]
    modules: List[Event]


class Summary:
    """One traced window, reduced."""

    def __init__(self, devices: List[Device], host: List[Event],
                 window: Tuple[float, float]):
        self.devices = devices
        self.host = host
        self.lo, self.hi = window
        self.window_s = self.hi - self.lo
        self._busy = [union(clip([(o.start, o.end) for o in d.ops],
                                 self.lo, self.hi)) for d in devices]
        self.busy_s = (sum(total(b) for b in self._busy) / len(devices)
                       if devices else 0.0)

    # ---- shares and sums, each a mean over the chips --------------------
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _mean(self, per_device) -> float:
        return sum(per_device) / len(per_device) if per_device else 0.0

    def op_time(self, match) -> float:
        """Seconds of device ops for which ``match(event)`` holds, the
        union of their intervals, mean over chips."""
        return self._mean([
            total(union(clip([(o.start, o.end) for o in d.ops if match(o)],
                             self.lo, self.hi)))
            for d in self.devices])

    def op_calls(self, match) -> float:
        """Number of device op events for which ``match(event)`` holds and
        that lie wholly in the window, mean over chips."""
        return self._mean([
            sum(1 for o in d.ops if match(o) and o.start >= self.lo
                and o.end <= self.hi) for d in self.devices])

    def module_time(self, prefix: str) -> float:
        """Seconds of runs of programs named ``prefix``..., mean over
        chips."""
        return self._mean([
            total(union(clip([(m.start, m.end) for m in d.modules
                              if module_name(m.name).startswith(prefix)],
                             self.lo, self.hi)))
            for d in self.devices])

    def idle_between(self, before: str, after: str) -> List[float]:
        """Device-idle seconds from the end of each run of a program named
        ``before``... to the start of the next run of one named
        ``after``..., on every chip."""
        out = []
        for d, busy in zip(self.devices, self._busy):
            mods = sorted((m for m in d.modules
                           if self.lo <= m.start and m.end <= self.hi),
                          key=lambda m: m.start)
            last_end = None
            for m in mods:
                name = module_name(m.name)
                if name.startswith(before):
                    last_end = m.end
                elif name.startswith(after) and last_end is not None:
                    out.append(total(gaps(busy, last_end, m.start)))
                    last_end = None
        return out

    # ---- the breakdown the result line carries --------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """The op names with most device self time (an op's time less
        that of the ops nested in it, such as a loop's body), in seconds
        per chip."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for name, t in self_times(d.ops, self.lo, self.hi):
                acc[name] = acc.get(name, 0.0) + t
        k = max(1, len(self.devices))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest idle gaps of the first chip, each named by what the
        host was doing: the shortest (innermost) host event that covers
        at least half of the gap, else the one that covers most of it."""
        if not self.devices:
            return []
        idle = sorted(gaps(self._busy[0], self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in idle:
            best, best_key = "idle", None
            for h in self.host:
                ov = min(e, h.end) - max(s, h.start)
                if ov <= 0:
                    continue
                half = ov >= 0.5 * (e - s)
                key = (half, -(h.end - h.start) if half else ov)
                if best_key is None or key > best_key:
                    best, best_key = h.name, key
            out.append([best, e - s])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def hlo_categories(path: str) -> Dict[Tuple[str, str], str]:
    """{(program id, HLO op name): category} of the programs in the
    trace, as the TPU profiler's own op statistics (``xprof``'s
    ``hlo_stats`` tool) classify them: "convolution fusion", "loop
    fusion", "data formatting", "custom-call" and so on. Empty where that
    tool is not installed."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return {}
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    out = {}
    for row in table["rows"]:
        v = dict(zip(cols, (c.get("v") for c in row["c"])))
        out[(str(v["program_id"]), str(v["hlo_op_name"]))] = str(
            v["category"])
    return out


def _program_id(module_event_name: str) -> str:
    """``jit_chunk(6713476272010182457)`` -> ``6713476272010182457``."""
    return module_event_name.rsplit("(", 1)[-1].rstrip(")")


def _categorise(ops: List[Event], modules: List[Event], categories):
    """Each op event, with its HLO category (from the program whose run
    encloses it) in its stats under ``hlo_category``."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        cat = None
        if i >= 0 and o.start <= mods[i].end:
            cat = categories.get((_program_id(mods[i].name),
                                  op_name(o.name)))
        if cat is not None:
            o = o._replace(stats=dict(o.stats, hlo_category=cat))
        out.append(o)
    return out


def reduce_file(path: str, chips: int) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    categories = hlo_categories(path)
    devices, host = [], []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            ops = _categorise(ops, modules, categories)
            devices.append(Device(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in _events(line):
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start, ev.end)
                    else:
                        host.append(ev)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PREFIX):]))
    return Summary(devices[:chips], host, window)


def reduce_dir(trace_dir: str, chips: int) -> Summary:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {files}")
    return reduce_file(files[0], chips)
