"""From a profiler trace and the compiled program to per-layer numbers.

``start``/``stop`` take a ``jax.profiler`` trace of the window into a
temporary directory and read it back as a ``Trace``: the device
operations of each chip (the "XLA Ops" line of each TPU plane) and the
benchmark's host spans, on one clock, in nanoseconds. The rest are pure
functions over intervals, so that a hand-built trace tests them.
"""
from __future__ import annotations

import base64
import glob
import os
import re
import shutil
import sys
import tempfile
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")


@dataclass
class Trace:
    """``devices``: each chip's innermost operations ``(name, t0, t1)``
    (an operation that holds others, such as a ``while``, is left out);
    ``busy``: each chip's intervals in which any operation ran (all
    levels; from ``devices`` when not given); ``spans``: the
    benchmark's host spans."""

    devices: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    busy: dict | None = None

    def busy_intervals(self, dev) -> list:
        if self.busy is not None:
            return self.busy[dev]
        return merged([(a, b) for _, a, b in self.devices[dev]])

    def window(self) -> tuple[float, float]:
        """The traced window: the benchmark's first host span to its last,
        or the devices' first operation to their last."""
        if self.spans:
            return (min(s[1] for s in self.spans),
                    max(s[2] for s in self.spans))
        ops = [e for evs in self.devices.values() for e in evs]
        return min(e[1] for e in ops), max(e[2] for e in ops)


def start() -> str:
    import jax

    path = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(path)
    return path


def stop(path: str) -> Trace:
    import jax

    jax.profiler.stop_trace()
    try:
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {path}")
        return read(files[0])
    finally:
        shutil.rmtree(path, ignore_errors=True)


def read(xplane: str) -> Trace:
    from jax.profiler import ProfileData

    out = Trace()
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                print(f"trace: {plane.name} has no {OPS_LINE!r} line "
                      f"({sorted(lines)})", file=sys.stderr)
                continue
            ops = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[OPS_LINE].events]
            out.devices[dev] = innermost(ops)
            out.busy = out.busy or {}
            out.busy[dev] = merged([(a, b) for _, a, b in ops])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out.spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
    return out


def op_name(text: str) -> str:
    """An op event's HLO instruction name: the trace names an operation
    by its whole HLO text, ``%fusion.12 = bf16[...] fusion(...), ...``."""
    m = re.match(r"%?([^\s=]+)\s*=", text)
    return m.group(1) if m else text


def innermost(events) -> list:
    """The events that hold no other event (a ``while`` holds its body's
    operations on the same line)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    holds, stack = set(), []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            holds.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(events) if i not in holds]


# -- intervals ---------------------------------------------------------------

def union(intervals) -> float:
    """Length covered by the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def merged(intervals) -> list:
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def busy_s(trace: Trace, leave_out=None) -> float:
    """Seconds in which an operation ran, averaged over the chips; with
    ``leave_out(name)``, less the time of the operations it selects."""
    lo, hi = trace.window()
    per = []
    for dev, evs in trace.devices.items():
        busy = union(clip(trace.busy_intervals(dev), lo, hi))
        if leave_out is not None:
            busy -= union([(a, b) for n, a, b in clip_events(evs, lo, hi)
                           if leave_out(n)])
        per.append(busy)
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_seconds(trace: Trace, pick) -> float:
    """Summed device time of the operations ``pick(name)`` selects,
    averaged over the chips."""
    lo, hi = trace.window()
    totals = [sum(b - a for n, a, b in clip_events(evs, lo, hi) if pick(n))
              for evs in trace.devices.values()]
    return sum(totals) / len(totals) / 1e9 if totals else 0.0


def clip_events(events, lo, hi) -> list:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


# -- breakdown ---------------------------------------------------------------

def top_ops(trace: Trace, k: int = 10) -> list:
    """The device operations that took most time, summed by name and
    averaged over the chips: ``[[name, seconds], ...]``."""
    lo, hi = trace.window()
    acc: dict = {}
    for evs in trace.devices.values():
        for n, a, b in clip_events(evs, lo, hi):
            acc[n] = acc.get(n, 0.0) + (b - a)
    n_dev = max(len(trace.devices), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_dev / 1e9] for n, t in top]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """The longest gaps in the first chip's operations, each named by the
    host span that covers most of it: ``[[name, seconds], ...]``."""
    if not trace.devices:
        return []
    lo, hi = trace.window()
    busy = merged(clip(trace.busy_intervals(min(trace.devices)), lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        best, name = 0.0, "host"
        for n, c, d in trace.spans:
            over = min(b, d) - max(a, c)
            if over > best:
                best, name = over, n
        out.append([name, (b - a) / 1e9])
    return out


# -- the compiled program ------------------------------------------------------

_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
          "f8e4m3fn": 1, "f8e5m2": 1}


def shapes_bytes(text: str) -> tuple[int, int]:
    """(elements of the first shape, bytes of all shapes) in ``text``."""
    first, total = None, 0
    for dt, dims in _SHAPE.findall(text):
        if dt not in _BYTES:
            continue
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        first = n if first is None else first
        total += n * _BYTES[dt]
    return first or 0, total


def _braced(line: str, key: str) -> str:
    """The text inside the braces that open with ``key``."""
    i = line.find(key)
    if i < 0:
        return ""
    i += len(key)
    depth = 1
    for j in range(i, len(line)):
        depth += {"{": 1, "}": -1}.get(line[j], 0)
        if depth == 0:
            return line[i:j]
    return line[i:]


def custom_calls(hlo: str) -> list[dict]:
    """Every Pallas kernel in compiled HLO text: its instruction name,
    the text of its operands' and results' shapes, and the decoded
    kernel body (which holds the kernel's Python function names)."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(",
                     line)
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        if not m:
            continue
        out.append({
            "name": m.group(1), "results": m.group(2),
            "operands": _braced(line, "operand_layout_constraints={"),
            "body": base64.b64decode(body.group(1)) if body else b"",
        })
    return out
