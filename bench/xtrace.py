"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's device
numbers: busy and idle time, time per device operation and per kernel, and
the longest idle gaps named by the benchmark's host span they fall in.

Device operations are the events of the "XLA Ops" line of each
"/device:..." plane; nested events on that line are counted once through
the union of their intervals. Each operation also carries the program
source file that made it (the HLO metadata's "source" stat), so device
time can be summed by module; an op other than a Pallas call is named
with that file too ("fusion u32[29696] @lsh/pstable.py"). Host spans are the "bench.*" annotations the
drivers write (`jax.profiler.TraceAnnotation`); a gap is named by the
innermost one it falls in and by the jitted call the host dispatched last
before the gap ended (JAX's own "PjitFunction(...)" host events), the call
the device was waiting for. All times are nanoseconds on the profiler's
common clock, in whole nanoseconds as `jax.profiler.ProfileData` gives
them.
"""

from __future__ import annotations

import bisect
import glob
import importlib.util
import os
import re
from typing import NamedTuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
DISPATCH_PREFIX = "PjitFunction("


class Event(NamedTuple):
    start: float
    end: float
    name: str
    source: str = ""     # device ops: the source file that made the op


class Trace(NamedTuple):
    devices: list[list[Event]]   # per device plane, its ops
    spans: list[Event]           # the benchmark's host spans
    dispatches: list[Event] = []  # jitted calls, by start time


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{log_dir}, want 1")
    return paths[0]


def op_name(text: str) -> str:
    """An "XLA Ops" event is named by its HLO instruction,
    "%roi_filter_pallas.8 = (f32[32,14848,1]...) custom-call(...)". A
    Pallas call keeps its wrapper's name ("roi_filter_pallas"); any other
    op reads as its kind and first result shape ("fusion u32[475136]"), so
    ops group by what they compute, not by their numbering."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    if name.endswith("_pallas") or shape is None:
        return name
    return f"{name} {shape.group(1)}"


def _xplane_pb2():
    """The XSpace protobuf classes, loaded by file path from the installed
    TensorFlow's copy of tsl, so that TensorFlow itself is not imported."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("the xplane protobuf needs TensorFlow's tsl copy")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _stat_str(stats, names: dict, want: str) -> str:
    for st in stats:
        if names.get(st.metadata_id) == want:
            return st.str_value
    return ""


def load(path: str) -> Trace:
    pb = _xplane_pb2()
    space = pb.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, spans, calls = [], [], []
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith("/device:"):
            names = {k: v.name for k, v in plane.stat_metadata.items()}
            source: dict[int, str] = {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = []
                for e in line.events:
                    if e.metadata_id not in source:
                        md = meta[e.metadata_id]
                        src = _stat_str(md.stats, names, "source")
                        source[e.metadata_id] = src.rpartition(":")[0]
                    start = line.timestamp_ns + e.offset_ps // 1000
                    src = source[e.metadata_id]
                    name = op_name(meta[e.metadata_id].name)
                    if src and not name.endswith("_pallas"):
                        name += " @" + "/".join(src.split("/")[-2:])
                    ops.append(Event(start, start + e.duration_ps // 1000,
                                     name, src))
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if not name.startswith((SPAN_PREFIX, DISPATCH_PREFIX)):
                        continue
                    start = line.timestamp_ns + e.offset_ps // 1000
                    end = start + e.duration_ps // 1000
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Event(start, end, name))
                    else:
                        calls.append(Event(start, end,
                                           name[len(DISPATCH_PREFIX):-1]))
    return Trace(devices, spans, sorted(calls))


def union(events: list[Event], t0: float, t1: float
          ) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covered by `events`, clipped to
    [t0, t1]."""
    ivs = sorted((max(e.start, t0), min(e.end, t1)) for e in events
                 if e.end > t0 and e.start < t1)
    out: list[list[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: list[Event], t0: float, t1: float) -> float:
    return sum(b - a for a, b in union(events, t0, t1))


def gaps(events: list[Event], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    """The idle intervals of [t0, t1]: where no device event runs."""
    out, at = [], t0
    for a, b in union(events, t0, t1):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def self_time(events: list[Event], t0: float, t1: float,
              key=lambda e: e.name) -> dict[str, tuple[float, int]]:
    """{key: (self ns inside [t0, t1], count)}. Events on the ops line
    nest (a while loop holds the ops of its body), so each event counts
    only the time none of the events inside it covers."""
    clipped = sorted(((max(e.start, t0), -min(e.end, t1), key(e))
                      for e in events if e.end > t0 and e.start < t1))
    out: dict[str, list[float]] = {}
    stack: list[list] = []    # [end, key, start, child ns], outermost first

    def close(entry):
        end, name, start, child = entry
        acc = out.setdefault(name, [0.0, 0])
        acc[0] += (end - start) - child
        acc[1] += 1
        if stack:
            stack[-1][3] += end - start

    for start, neg_end, name in clipped:
        end = -neg_end
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([end, name, start, 0.0])
    while stack:
        close(stack.pop())
    return {k: (v[0], int(v[1])) for k, v in out.items()}


def host_span_at(spans: list[Event], t: float) -> str:
    """The innermost benchmark span that holds time t, or "none"."""
    return spans_at(spans, [t])[0]


def spans_at(spans: list[Event], times: list[float]) -> list[str]:
    """The innermost benchmark span holding each time, or "none": one sweep
    over the spans by start time."""
    order = sorted(range(len(times)), key=times.__getitem__)
    by_start = sorted(spans)
    out = ["none"] * len(times)
    live: list[Event] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(by_start) and by_start[j].start <= t:
            live.append(by_start[j])
            j += 1
        live = [s for s in live if s.end > t]
        if live:
            out[i] = min(live, key=lambda s: s.end - s.start).name
    return out


def gap_names(trace: Trace, gaps_: list[tuple[float, float]]) -> list[str]:
    """What the host was doing in each idle gap [a, b]: its benchmark span,
    and the jitted call dispatched last before the gap ended."""
    names = spans_at(trace.spans, [(a + b) / 2 for a, b in gaps_])
    out = []
    for name, (_, b) in zip(names, gaps_):
        i = bisect.bisect_left(trace.dispatches, (b,))
        out.append(f"{name} > {trace.dispatches[i - 1].name}" if i else name)
    return out


class Summary(NamedTuple):
    window_ns: float
    busy_ns: float                               # averaged over devices
    ops: dict[str, tuple[float, int]]            # summed over devices
    gaps: list[tuple[str, float]]                # (host span, ns), longest first
    sources: dict[str, tuple[float, int]] = {}   # by source file, summed


def summarize(trace: Trace, t0: float, t1: float) -> Summary:
    """Reduce [t0, t1] of a trace."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    busy = [busy_ns(ops, t0, t1) for ops in trace.devices]
    ops: dict[str, tuple[float, int]] = {}
    sources: dict[str, tuple[float, int]] = {}
    named: list[tuple[str, float]] = []
    for dev in trace.devices:
        for into, key in ((ops, lambda e: e.name),
                          (sources, lambda e: e.source)):
            for name, (ns, count) in self_time(dev, t0, t1, key).items():
                old = into.get(name, (0.0, 0))
                into[name] = (old[0] + ns, old[1] + count)
        idle = gaps(dev, t0, t1)
        named += [(name, b - a)
                  for name, (a, b) in zip(gap_names(trace, idle), idle)]
    named.sort(key=lambda g: -g[1])
    return Summary(t1 - t0, sum(busy) / len(busy), ops, named, sources)


def window(trace: Trace, name: str) -> tuple[float, float]:
    """(start, end) of the one host span called `name`."""
    hits = [s for s in trace.spans if s.name == name]
    if len(hits) != 1:
        raise ValueError(f"{len(hits)} host spans named {name!r}, want 1")
    return hits[0].start, hits[0].end


def kernel_ns(summary: Summary, pattern: str) -> tuple[float, int]:
    """Device time and launch count of the ops whose name matches the
    regular expression `pattern`."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary.ops.items() if rx.search(k)]
    return sum(h[0] for h in hits), sum(h[1] for h in hits)


def source_ns(summary: Summary, pattern: str) -> tuple[float, int]:
    """Device self time and op count of the ops whose source file matches
    the regular expression `pattern`."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary.sources.items() if k and rx.search(k)]
    return sum(h[0] for h in hits), sum(h[1] for h in hits)


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v[0] / 1e9] for k, v in ops],
            "idle_gaps": [[k, ns / 1e9] for k, ns in summary.gaps[:top]]}
