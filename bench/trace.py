"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

What a TPU trace holds, as read by hand from one of this system's runs on
a TPU v5 lite:

* one plane per chip, named ``/device:TPU:<i>``; its line ``XLA Ops``
  holds one event per executed HLO op, named by the op's HLO text
  (``%fusion.237 = f32[...] fusion(...)``), nested: a ``while`` op's event
  spans the events of its body.  ``XLA Modules`` holds one event per
  program run.  A Pallas kernel is a ``custom-call`` whose text carries
  ``custom_call_target="tpu_custom_call"``.
* host planes (``/host:CPU``) with one line per thread; spans the
  benchmark opens with ``jax.profiler.TraceAnnotation`` appear there by
  name.

Times are nanoseconds on one clock for host and device events.
"""
from __future__ import annotations

import dataclasses
import re

Interval = tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str          # HLO text of the op
    start: float       # ns
    end: float         # ns

    @property
    def short(self) -> str:
        """``fusion.237``, or ``closed_call.11[tpu_custom_call]``."""
        head = self.name.split(" = ", 1)[0].lstrip("%").strip()
        target = re.search(r'custom_call_target="([^"]+)"', self.name)
        return f"{head}[{target.group(1)}]" if target else head


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Op]]          # chip id -> its XLA ops
    annotations: dict[str, list[Interval]]  # host span name -> intervals


PREFIX = "bench:"   # the benchmark's host spans in a trace


def load(path: str) -> Trace:
    """Read the device ops of every TPU plane and the benchmark's host
    spans (named ``bench:<span>``; the prefix is stripped)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[int, list[Op]] = {}
    notes: dict[str, list[Interval]] = {}
    for plane in data.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if dev and line.name == "XLA Ops":
                devices[int(dev.group(1))] = [
                    Op(e.name, e.start_ns, e.end_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        notes.setdefault(e.name[len(PREFIX):], []).append(
                            (e.start_ns, e.end_ns))
    return Trace(devices, notes)


def union(intervals) -> list[Interval]:
    """Sorted disjoint union of intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, window: list[Interval]) -> list[Interval]:
    """The parts of ``intervals`` inside the union ``window``."""
    out = []
    for a, b in union(intervals):
        for wa, wb in window:
            lo, hi = max(a, wa), min(b, wb)
            if hi > lo:
                out.append((lo, hi))
    return union(out)


def gaps(busy: list[Interval], window: list[Interval]) -> list[Interval]:
    """The parts of ``window`` in which nothing in ``busy`` ran."""
    out = []
    for wa, wb in union(window):
        t = wa
        for a, b in union(busy):
            if b <= t or a >= wb:
                continue
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < wb:
            out.append((t, wb))
    return out


def self_times(ops: list[Op], window: list[Interval]) -> dict[str, float]:
    """Exclusive device time per op (ns inside ``window``): an op's time
    less that of the ops nested in it, summed by ``Op.short``."""
    out: dict[str, float] = {}
    stack: list[tuple[Op, float]] = []   # (op, time of its children)

    def close(op: Op, children: float) -> None:
        inside = length(clip([(op.start, op.end)], window))
        own = inside - children
        if own > 0:
            out[op.short] = out.get(op.short, 0.0) + own

    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= op.start:
            done, kids = stack.pop()
            close(done, kids)
        if stack:
            parent, kids = stack[-1]
            stack[-1] = (parent, kids + length(
                clip([(op.start, min(op.end, parent.end))], window)))
        stack.append((op, 0.0))
    while stack:
        done, kids = stack.pop()
        close(done, kids)
    return out


def busy_per_chip(tr: Trace, chips: int, window: list[Interval]
                  ) -> list[float]:
    """Device busy time (ns) inside ``window`` of the first ``chips``
    chips in the trace."""
    return [length(clip([(o.start, o.end) for o in tr.devices[i]], window))
            for i in sorted(tr.devices)[:chips]]


def top(items: dict[str, float], k: int = 10) -> list[list]:
    return [[n, v] for n, v in sorted(items.items(), key=lambda x: -x[1])[:k]]
