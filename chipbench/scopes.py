"""Device time by named stage and idle time by host span, read from a
JAX profiler trace (``.xplane.pb``) over the window ``xplane.py`` uses.

* Stages: the tick program's stages run under ``jax.named_scope``
  (``deaths``, ``fold``, ``refresh``, ``switch``), which XLA keeps in each
  op's ``tf_op`` path (``jit(_tick_impl)/deaths/while/body/...``).  The
  path is a stat of the op's event metadata, which
  ``jax.profiler.ProfileData`` does not expose, so this module parses the
  XSpace protobuf itself.  Each op that starts inside a module of the
  program (``jit__tick_impl...``) gives its self time, by the rule of
  ``xplane.self_times``, to the first stage its path names, else to
  ``UNSCOPED``.
* Idle: each instant in which no op runs on the first device is given to
  the innermost host event on the thread that runs the probe periods
  (the program's spans, ``transport.admit`` and the like, the
  simulator's per-event spans, ``Captain.fail`` and the like, or a
  runtime event), else to ``UNNAMED``.

The message classes are built from a descriptor of the few fields read
(field numbers of ``tsl/profiler/protobuf/xplane.proto``; the parser
skips the others), so neither TensorFlow nor a generated module is
needed.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from chipbench import xplane

PROGRAM = "jit__tick_impl"
STAGES = ("deaths", "fold", "refresh", "switch")
UNSCOPED = "unscoped"
UNNAMED = "unnamed"

_INT64, _UINT64, _STRING, _MESSAGE = 3, 4, 9, 11
_ONE, _MANY = 1, 3
_FIELDS = {     # message: (field, number, type, label, message type)
    "XSpace": [("planes", 1, _MESSAGE, _MANY, "XPlane")],
    "XPlane": [("name", 2, _STRING, _ONE, None),
               ("lines", 3, _MESSAGE, _MANY, "XLine"),
               ("event_metadata", 4, _MESSAGE, _MANY, "EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, _MANY, "StatMetadataEntry")],
    # the two map fields, as the repeated entries they are on the wire
    "EventMetadataEntry": [("key", 1, _INT64, _ONE, None),
                           ("value", 2, _MESSAGE, _ONE, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64, _ONE, None),
                          ("value", 2, _MESSAGE, _ONE, "XStatMetadata")],
    "XLine": [("name", 2, _STRING, _ONE, None),
              ("timestamp_ns", 3, _INT64, _ONE, None),
              ("events", 4, _MESSAGE, _MANY, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT64, _ONE, None),
               ("offset_ps", 2, _INT64, _ONE, None),
               ("duration_ps", 3, _INT64, _ONE, None)],
    "XStat": [("metadata_id", 1, _INT64, _ONE, None),
              ("str_value", 5, _STRING, _ONE, None),
              ("ref_value", 7, _UINT64, _ONE, None)],
    "XEventMetadata": [("name", 2, _STRING, _ONE, None),
                       ("stats", 5, _MESSAGE, _MANY, "XStat")],
    "XStatMetadata": [("name", 2, _STRING, _ONE, None)],
}


def _message_classes() -> dict:
    package = "chipbench.xplane"
    proto = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package=package, syntax="proto2")
    for name, fields in _FIELDS.items():
        msg = proto.message_type.add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=label)
            if tname:
                f.type_name = f".{package}.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.{name}")) for name in _FIELDS}


MESSAGES = _message_classes()
XSpace = MESSAGES["XSpace"]


@dataclasses.dataclass
class Scopes:
    steps: int
    program_ms: float       # device ms of the program's modules
    stage_ms: dict          # stage (or UNSCOPED) -> device self ms
    idle_ms: dict           # innermost host event (or UNNAMED) -> ms

    def per_step(self, table: dict) -> dict:
        return {k: v / self.steps for k, v in table.items()}


def load(path) -> "XSpace":
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _events(line):
    """(metadata id, start ns, end ns) of a line's events, in whole ns as
    ``jax.profiler.ProfileData`` gives them to ``xplane.py``."""
    t0 = line.timestamp_ns
    for ev in line.events:
        s = t0 + ev.offset_ps // 1000
        yield ev.metadata_id, s, s + ev.duration_ps // 1000


def _stages(plane) -> dict:
    """{event metadata id: stage} from each op's ``tf_op`` path."""
    stat = {e.key: e.value.name for e in plane.stat_metadata}
    tf_op = {k for k, n in stat.items() if n == "tf_op"}
    out = {}
    for entry in plane.event_metadata:
        path = ""
        for st in entry.value.stats:
            if st.metadata_id in tf_op:
                path = st.str_value or stat.get(st.ref_value, "")
        out[entry.key] = next(
            (part for part in path.split("/") if part in STAGES), UNSCOPED)
    return out


def _innermost(events, lo, hi) -> list:
    """[(start, end, name)] cutting [lo, hi] by the latest-starting event
    that covers each instant (``None`` where none does)."""
    events = sorted(events, key=lambda ev: ev[1])
    cuts = sorted({lo, hi} | {t for _, s, e in events for t in (s, e)
                              if lo < t < hi})
    out, open_, i = [], [], 0      # open_: heap, latest start (then the
    for a, b in zip(cuts, cuts[1:]):   # earliest end) on top
        while i < len(events) and events[i][1] <= a:
            name, s, e = events[i]
            heapq.heappush(open_, (-s, e, i, name))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        out.append((a, b, open_[0][3] if open_ else None))
    return out


def reduce(space) -> Scopes:
    planes = list(space.planes)
    steps, step_line, step_names = [], None, None
    for plane in planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            found = [(s, e) for m, s, e in _events(line)
                     if names.get(m) == xplane.STEP_NAME]
            if len(found) > len(steps):
                steps, step_line, step_names = found, line, names
    if not steps:
        raise ValueError(f"no {xplane.STEP_NAME!r} step annotations")
    steps.sort()
    lo, hi = steps[0][0], steps[-1][1]

    devices = [p for p in planes if xplane.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lines = [{ln.name: ln for ln in p.lines} for p in devices]
    for by_name in lines:
        if xplane.MODULES_LINE in by_name:
            for _, s, e in _events(by_name[xplane.MODULES_LINE]):
                if s >= lo:
                    hi = max(hi, e)

    program_ns, stage_ns, busy0 = 0.0, {}, None
    for plane, by_name in zip(devices, lines):
        names = {e.key: e.value.name for e in plane.event_metadata}
        mods = sorted((s, e) for m, s, e in (
            _events(by_name[xplane.MODULES_LINE])
            if xplane.MODULES_LINE in by_name else ())
            if lo <= s < hi and names.get(m, "").startswith(PROGRAM))
        program_ns += sum(e - s for s, e in mods)
        starts = [s for s, _ in mods]
        ops = list(_events(by_name[xplane.OPS_LINE])) \
            if xplane.OPS_LINE in by_name else []
        stage = _stages(plane)
        inside = []
        for m, s, e in ops:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < mods[k][1]:
                inside.append((stage.get(m, UNSCOPED), s, e))
        for key, t in xplane.self_times(inside).items():
            stage_ns[key] = stage_ns.get(key, 0.0) + t
        if busy0 is None:
            busy0 = xplane._union([(max(s, lo), min(e, hi))
                                   for _, s, e in ops if e > lo and s < hi])

    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = [(step_names.get(m, ""), s, e) for m, s, e in _events(step_line)
            if step_names.get(m) != xplane.STEP_NAME and e > lo and s < hi]
    segs = _innermost(host, lo, hi)
    seg_starts = [a for a, _, _ in segs]
    idle_ns = {}
    for s, e in idle:
        k = max(bisect.bisect_right(seg_starts, s) - 1, 0)
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                key = name or UNNAMED
                idle_ns[key] = idle_ns.get(key, 0.0) + overlap
            k += 1
    return Scopes(steps=len(steps), program_ms=program_ns * 1e-6,
                  stage_ms={k: v * 1e-6 for k, v in stage_ns.items()},
                  idle_ms={k: v * 1e-6 for k, v in idle_ns.items()})


def read(path) -> Scopes:
    return reduce(load(path))
