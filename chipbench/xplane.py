"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The traced window runs from the start of the first ``probe_period`` step
annotation on the host to the end of the last, or to the end of the
device work those steps queued, whichever is later (the measured window
ends in ``block_until_ready`` too).  Inside it:

* busy: the union of the intervals in which an XLA op ran on a TPU
  device (the device planes' ``XLA Ops`` line), averaged over devices;
* per-module device time: the ``XLA Modules`` line's events, summed by
  module name prefix (``jit__tick_impl`` and so on), with their count;
* the ten device ops that took most time, by self time (an op's time
  less that of the ops nested in it, such as a loop's body) summed over
  the HLO instruction's name and opcode;
* the ten longest idle gaps, each labelled with the probe period it falls
  in and what the host was doing at its midpoint: the innermost Python
  frame of the profiler's Python tracer outside the builtins where the
  trace has one, else the innermost runtime event on the thread that
  runs the steps, else "host outside JAX calls" (the host runs its own
  Python there).
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

import numpy as np

STEP_NAME = "probe_period"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    steps: int
    modules: dict           # module name -> (seconds, count)
    top_ops: list           # [[name, seconds]]
    gaps: list              # [[label, seconds]]

    def module_s(self, prefix: str):
        """(seconds, count) summed over modules named ``prefix...``."""
        hits = [v for k, v in self.modules.items() if k.startswith(prefix)]
        if not hits:
            return None
        return sum(s for s, _ in hits), sum(n for _, n in hits)


def find(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_OPCODE = re.compile(r" = .*?\b([a-z][a-z-]*)\(")


def op_label(text: str) -> str:
    """``%fusion.278 fusion`` from an XLA op event's HLO text."""
    m = _OPCODE.search(text)
    name = text.split(" = ", 1)[0]
    return f"{name} {m.group(1)}" if m else name


def self_times(events):
    """{label: self ns} of (name, start, end) events that nest."""
    out, stack = {}, []
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0) - (e - s)
        label = op_label(n)
        out[label] = out.get(label, 0) + (e - s)
        stack.append((label, e))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _is_python(line) -> bool:
    """A line of the profiler's Python tracer (frames named ``$...``)."""
    first = next(iter(line.events), None)
    return first is not None and first.name.startswith("$")


def _innermost(lines, points, skip: str) -> list:
    """For each time point, the name of the latest-starting event that
    covers it on ``lines``, leaving out names that start with ``skip``."""
    names, starts, ends = [], [], []
    for line in lines:
        for n, s, e in _events(line):
            if not n.startswith(skip):
                names.append(n)
                starts.append(s)
                ends.append(e)
    starts, ends = np.asarray(starts), np.asarray(ends)
    out = []
    for p in points:
        hit = np.nonzero((starts <= p) & (ends > p))[0]
        out.append(names[hit[np.argmax(starts[hit])]] if hit.size else None)
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(path) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = list(data.planes)

    # the host thread that ran the steps, and the steps themselves
    steps, step_line = [], None
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            found = [(s, e) for n, s, e in _events(line) if n == STEP_NAME]
            if len(found) > len(steps):
                steps, step_line = found, line
    if not steps:
        raise ValueError(f"no {STEP_NAME!r} step annotations in {path}")
    steps.sort()
    lo, hi = steps[0][0], steps[-1][1]

    devices = [{ln.name: ln for ln in p.lines} for p in planes
               if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}: "
                         f"{[p.name for p in planes]}")
    for lines in devices:
        for _, s, e in _events(lines[MODULES_LINE]) \
                if MODULES_LINE in lines else ():
            if s >= lo:
                hi = max(hi, e)
    busy_ns, modules, ops, busy0 = 0, {}, {}, None
    for lines in devices:
        inside = [(n, max(s, lo), min(e, hi))
                  for n, s, e in (_events(lines[OPS_LINE])
                                  if OPS_LINE in lines else ())
                  if e > lo and s < hi]
        for label, t in self_times(inside).items():
            ops[label] = ops.get(label, 0) + t
        merged = _union([(s, e) for _, s, e in inside])
        busy_ns += sum(e - s for s, e in merged)
        if busy0 is None:
            busy0 = merged
        for n, s, e in _events(lines[MODULES_LINE]) \
                if MODULES_LINE in lines else ():
            if lo <= s < hi:
                t, c = modules.get(n, (0.0, 0))
                modules[n] = (t + (e - s) * 1e-9, c + 1)

    # idle gaps of the first device, labelled by step and host activity
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle = sorted(idle, key=lambda iv: iv[0] - iv[1])[:TOP]
    mids = [(s + e) / 2 for s, e in idle]
    python = [ln for p in planes if not DEVICE_PLANE.match(p.name)
              for ln in p.lines if _is_python(ln)]
    labels = _innermost(python, mids, skip="$builtins") if python else \
        [None] * len(mids)
    fallback = _innermost([step_line], mids, skip=STEP_NAME)
    gaps = []
    for (s, e), mid, a, b in zip(idle, mids, labels, fallback):
        step = next((i for i, (x, y) in enumerate(steps) if x <= mid < y),
                    None)
        what = a or b or "host outside JAX calls"
        label = f"period {step}: {what}" if step is not None else what
        gaps.append([label, (e - s) * 1e-9])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / len(devices),
        steps=len(steps), modules=modules,
        top_ops=[[n, t * 1e-9] for n, t in top_ops], gaps=gaps)
