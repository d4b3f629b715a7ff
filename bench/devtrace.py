"""From a profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs, as a plain "events" dict (ns):

  window   [start, end] of the harness's ``bench.window`` span
  steps    steps completed in it (``bench.wait`` spans)
  devices  per chip, its operations: [start, duration, name, category],
           from the chip's "XLA Ops" line; a loop's operations lie
           inside the loop's own event
  async    per chip, the collectives in flight: the same, from the
           chip's "Async XLA Ops" line
  host     the harness's spans: [start, duration, name]

The category is XLA's ``hlo_category``, which the trace keeps on each
operation's metadata, not on the event; ``jax.profiler.ProfileData``
does not show metadata, so the file is read with ``google.protobuf``
and the few fields of XLA's ``xplane.proto`` named in ``_SCHEMA``.

``Reduced`` clips every operation to the window, gives each instant to
the innermost operation running then, classes it as matmul, collective
or vector (everything else), and gives the per-step times that the
readers in ``bench/metrics/`` report; the step's operation count comes
from the cell's program module, and each op's part of the step
(forward, backward, optimizer) from the map of ``bench/scopes.py``.
"""
from __future__ import annotations

import collections
import glob
import os

from bench import scopes as scope_map

HOST_SPANS = ("bench.window", "bench.select", "bench.dispatch", "bench.wait")
# XLA's op categories on the TPU's "XLA Ops" line, by class
MATMUL_CATEGORIES = ("convolution", "convolution fusion", "custom-call",
                     "tpu_custom_call")
COLLECTIVE_WORDS = ("all-gather", "reduce-scatter", "all-reduce",
                    "collective-permute", "all-to-all")
# XLA's TPU backend runs some collectives (phantom's ghost all-gather) as
# an asynchronous collective fusion: a pair of ops of the category
# "custom fusion" that XLA names so; the collective is in flight from the
# start op to the end of the done op.
ASYNC_CATEGORY, ASYNC_PREFIX = "custom fusion", "async-collective-"

# message: [(field, number, type, repeated)]; a type in this table is a
# message, "str" a string, "int" an int64 and "uint" a uint64.  Map
# fields are read as their repeated key/value entries.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "str", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "str", False), ("timestamp_ns", 3, "int", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int", False),
               ("offset_ps", 2, "int", False),
               ("duration_ps", 3, "int", False)],
    "XStat": [("metadata_id", 1, "int", False), ("str_value", 5, "str", False),
              ("ref_value", 7, "uint", False)],
    "XEventMetadata": [("name", 2, "str", False),
                       ("display_name", 4, "str", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "str", False)],
}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"str": F.TYPE_STRING, "int": F.TYPE_INT64,
              "uint": F.TYPE_UINT64}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def classify(category: str, name: str = "") -> str:
    """matmul | collective | vector, from the category XLA gives the op
    (and, for an asynchronous collective fusion, the name XLA gives it)."""
    cat = category.lower()
    if any(w in cat for w in COLLECTIVE_WORDS) or (
            cat == ASYNC_CATEGORY and name.startswith(ASYNC_PREFIX)):
        return "collective"
    if cat in MATMUL_CATEGORIES:
        return "matmul"
    return "vector"


def _events(plane):
    """(line name, start ns, duration ns, name, hlo_category) of each
    event of a plane; the category is None where the op has none."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {}
    for e in plane.event_metadata:
        cat = None
        for st in e.value.stats:
            if stat_names.get(st.metadata_id) == "hlo_category":
                cat = st.str_value or stat_names.get(st.ref_value)
        meta[e.key] = (e.value.display_name or e.value.name, cat)
    for line in plane.lines:
        for ev in line.events:
            name, cat = meta.get(ev.metadata_id, ("", None))
            yield (line.name, line.timestamp_ns + ev.offset_ps / 1000,
                   ev.duration_ps / 1000, name, cat)


def _read(path: str):
    if os.path.isdir(path):
        paths = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(paths, key=os.path.getmtime)
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def trim(path: str, out: str):
    """Write the trace at ``path`` with only what ``load`` reads: the
    chips' two op lines, the harness's host spans, each op's short name
    and its ``hlo_category``."""
    space = _read(path)
    space.DiscardUnknownFields()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        meta = {e.key: e.value for e in plane.event_metadata}
        keep = [line for line in plane.lines if
                (line.name in ("XLA Ops", "Async XLA Ops") if device
                 else plane.name.startswith("/host:"))]
        for line in keep:
            evs = [ev for ev in line.events if device
                   or meta[ev.metadata_id].name in HOST_SPANS]
            del line.events[:]
            line.events.extend(evs)
        keep = [line for line in keep if line.events]
        del plane.lines[:]
        plane.lines.extend(keep)
        used = {ev.metadata_id for line in keep for ev in line.events}
        cat = [e.key for e in plane.stat_metadata
               if e.value.name == "hlo_category"]
        refs = set(cat)
        entries = []
        for e in plane.event_metadata:
            if e.key not in used:
                continue
            stats = [st for st in e.value.stats if st.metadata_id in cat]
            del e.value.stats[:]
            e.value.stats.extend(stats)
            refs |= {st.ref_value for st in stats}
            if e.value.display_name:
                e.value.name = ""
            entries.append(e)
        del plane.event_metadata[:]
        plane.event_metadata.extend(entries)
        stat = [e for e in plane.stat_metadata if e.key in refs]
        del plane.stat_metadata[:]
        plane.stat_metadata.extend(stat)
    with open(out, "wb") as f:
        f.write(space.SerializeToString())


def _in_flight(ops):
    """[start, duration, name, category] of each asynchronous collective
    fusion, from its start op to the end of its done op."""
    out, started = [], collections.deque()
    for s, d, name, c in sorted(ops):
        if c != ASYNC_CATEGORY:
            continue
        if name.startswith(ASYNC_PREFIX + "start"):
            started.append(s)
        elif name.startswith(ASYNC_PREFIX + "done") and started:
            t = started.popleft()
            out.append([t, s + d - t, ASYNC_PREFIX + "in-flight", c])
    return out


def load(path: str, chips: int) -> dict:
    """The events of a trace: an ``.xplane.pb`` file, or the newest one
    under a directory."""
    space = _read(path)
    devices, asyncs, host = {}, {}, []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            ordinal = int(plane.name.rsplit(":", 1)[1])
            ops, flying = devices.setdefault(ordinal, []), []
            for line, start, dur, name, cat in _events(plane):
                if line == "XLA Ops":
                    if not cat:
                        raise ValueError(f"device op {name} carries no "
                                         "hlo_category in the trace")
                    ops.append([start, dur, name, cat])
                elif (line == "Async XLA Ops" and cat
                      and classify(cat, name) == "collective"):
                    flying.append([start, dur, name, cat])
            asyncs[ordinal] = flying + _in_flight(ops)
        elif plane.name.startswith("/host:"):
            host += [[start, dur, name]
                     for _, start, dur, name, _ in _events(plane)
                     if name in HOST_SPANS]
    used = sorted(d for d in devices if devices[d])
    if len(used) < chips:
        raise ValueError(f"trace has device ops for {used}, the run used "
                         f"{chips} chips")
    windows = [h for h in host if h[2] == "bench.window"]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} bench.window spans in the trace")
    w0, w1 = windows[0][0], windows[0][0] + windows[0][1]
    host = sorted(h for h in host if h[2] != "bench.window"
                  and w0 <= h[0] <= w1)
    return {"window": [w0, w1],
            "steps": sum(h[2] == "bench.wait" for h in host),
            "devices": [sorted(devices[d]) for d in used[:chips]],
            "async": [sorted(asyncs[d]) for d in used[:chips]],
            "host": host}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    left, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


def _innermost(ops):
    """Split nested ``(start, end, name, class)`` operations into the
    pieces in which each is the innermost one running: a loop keeps what
    its body's operations leave of it."""
    out, stack = [], []                 # [start, end, name, class, cursor]

    def close():
        _, e, name, c, cur = stack.pop()
        if e > cur:
            out.append((cur, e, name, c))
        if stack:
            stack[-1][4] = max(stack[-1][4], e)

    for s, e, name, c in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            top = stack[-1]
            if s > top[4]:
                out.append((top[4], s, top[2], top[3]))
            e = min(e, top[1])
        stack.append([s, e, name, c, s])
    while stack:
        close()
    return sorted(out)


class Reduced:
    """Per-chip, per-step times of one traced window of a cell's run.
    ``scopes`` is the map from op name to part of the step that
    ``scopes.program_scopes`` makes; one that names no scope leaves the
    scope readers at None."""

    def __init__(self, events: dict, cell, *, peak: dict, scopes: dict):
        self.cell, self.peak, self.scopes = cell, peak, scopes
        self.tp = cell.program.mesh_tp(cell.config, cell.chips)
        self.w0, self.w1 = events["window"]
        self.steps = events["steps"]
        self.host = events["host"]
        self.window_s = (self.w1 - self.w0) * 1e-9
        chips = len(events["devices"])
        flying = events.get("async") or [[]] * chips
        self.ops = []                   # per chip: (start, end, name, class)
        self.class_s = collections.Counter()
        exposed = 0.0
        for evs, fly in zip(events["devices"], flying):
            ops = _innermost(self._clip(evs))
            if not ops:
                raise ValueError("a chip ran no operation in the traced "
                                 "window: host and device clocks disagree")
            self.ops.append(ops)
            for s, e, _, c in ops:
                if c != "collective":
                    self.class_s[c] += (e - s) * 1e-9 / chips
            coll = _union([o[:2] for o in ops + self._clip(fly)
                           if o[3] == "collective"])
            comp = _union([o[:2] for o in ops if o[3] != "collective"])
            self.class_s["collective"] += _length(coll) * 1e-9 / chips
            exposed += _subtract(coll, comp)
        self.busy = [_union([o[:2] for o in ops]) for ops in self.ops]
        self.busy_s = sum(map(_length, self.busy)) / chips * 1e-9
        self.exposed_collective_s = exposed / chips * 1e-9

    def _clip(self, evs):
        """``(start, end, name, class)`` of each event, clipped to the
        window; events wholly outside it are dropped."""
        out = []
        for start, dur, name, cat in evs:
            s, e = max(start, self.w0), min(start + dur, self.w1)
            if e > s:
                out.append((s, e, name, classify(cat, name)))
        return out

    def per_step_ms(self, seconds: float):
        return 1e3 * seconds / self.steps

    def class_ms(self, cls: str):
        """Device time per step in one class, ms; None where none ran."""
        s = self.class_s.get(cls, 0.0)
        return self.per_step_ms(s) if s > 0 else None

    def host_ms(self, name: str):
        """Mean host time of one of the harness's spans, ms."""
        d = [h[1] for h in self.host if h[2] == name]
        return 1e-6 * sum(d) / len(d) if d else None

    def step_flops(self) -> float:
        c = self.cell
        return c.program.step_flops(c.config, self.tp, c.traffic)

    def matmul_floor_s(self):
        c = self.cell
        return c.program.matmul_floor_s(c.config, self.tp, c.traffic,
                                        self.peak)

    def _host_at(self, t):
        for s, d, name in self.host:
            if s <= t < s + d:
                return name.split(".", 1)[1]
        return "host_other"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, each named with its part
        of the step (``optimizer:fusion.20``) where the map names a
        scope, and the longest idle gaps by what the host was doing."""
        chips = len(self.ops)
        named = scope_map.names_a_scope(self.scopes)
        by_op = collections.Counter()
        for ops in self.ops:
            for s, e, name, _ in ops:
                if named:
                    name = f"{self.scopes.get(name, 'unscoped')}:{name}"
                by_op[name] += (e - s) * 1e-9 / chips
        gaps = []
        for d, busy in enumerate(self.busy):
            edges = [self.w0] + [x for iv in busy for x in iv] + [self.w1]
            gaps += [(e - s, s, d) for s, e in zip(edges[0::2], edges[1::2])
                     if e > s]
        gaps.sort(reverse=True)
        return {"device_ops": [[n, s] for n, s in by_op.most_common(top)],
                "idle_gaps": [[f"{self._host_at(s + g / 2)} chip{d}",
                               g * 1e-9] for g, s, d in gaps[:top]]}
