"""Plan steps and the runtime's copy work, read from a traced run's profile.

``trace.Trace`` keeps each device operation's instruction name and the
benchmark's own host spans. ``ScopedTrace`` keeps those and two things
more, in fields of its own:

- ``named``: each device operation's ``op_name`` metadata. A v5e trace
  keeps it in the ``tf_op`` stat of the event's metadata, which
  ``jax.profiler.ProfileData`` does not show, so the file is read with a
  description of the profiler's protobuf. The program names its jitted
  pipeline ``focus_<pipeline>`` and runs each plan step, and each
  complex <-> f32-plane conversion, under a ``jax.named_scope``, so an
  ``op_name`` reads ``jit(focus_fused3)/azimuth_fft/unsplit/add:``: the
  program, the scopes (``scope_path``), the operation. An ``op_name``
  with no ``jit(...)`` head is the name of the program's argument
  (``raw:``): XLA gives it to what the compiler does to the argument
  where it enters the program (on a v5e, the complex64 echo split into
  its f32 halves, ``X64SplitLow/High``), where no scope reaches.
- ``host``: the runtime's own events of copy work on every host thread
  (``RELAYOUT`` and ``TRANSFER``), on the clock of the device operations
  and the benchmark's spans. An event's kind is its name's first word:
  a v5e names the join of a complex64's halves ``X64FromTuple
  c64[4,4096,4096]{2,1,0}``.

From those: device seconds by plan step, the share of busy time spent
converting between complex64 and f32 planes (``glue_seconds``), and the
share of the copy spans in which some host thread converts a layout
(``host_share``).

A traced run reads its profile once, with ``ScopedTrace.from_xplane``,
and the per-layer readers take that reading from the run (``for_run``).
Given a plain ``trace.Trace``, ``for_run`` finds its profile under
``TMPDIR``, where ``trace.recording`` keeps it in a directory named
``sarbench_trace_*`` until the process ends: the one whose ``window`` span
is the trace's is read.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import tempfile
from typing import Iterable, Optional

from sarbench import trace as T

OP_NAME_STAT = "tf_op"
PROGRAM_PREFIX = "focus_"          # the jitted pipeline: focus_<pipeline>
GLUE_SCOPES = ("split", "unsplit")
ARGUMENT = "argument"              # ops named after the program's argument
# the runtime's host events of copy work on a v5e: converting the host's
# row-major layout into the chip's tiled one (Linearize), the tiled one
# back (Transpose::ExecuteChunk), and a complex64's two f32 halves, as the
# chip keeps them, joined into the host's interleaved pairs (X64FromTuple);
# then the transfers themselves
RELAYOUT = ("Linearize", "Transpose::ExecuteChunk", "X64FromTuple")
TRANSFER = ("tpu::System::TransferToDevice",
            "tpu::System::TransferFromDevice")
COPY_SPANS = ("h2d", "d2h")
RECORDING_GLOB = os.path.join("sarbench_trace_*", "**", "*.xplane.pb")

_JIT = re.compile(r"^jit\((.*)\)$")


def scope_path(op_name: Optional[str]) -> tuple:
    """``(program, scopes)`` of an ``op_name``:
    ``jit(focus_fused3)/azimuth_fft/split/real:`` ->
    ``("focus_fused3", ("azimuth_fft", "split"))``, the operation's own
    name dropped. ``raw:`` -> ``(None, ("raw:",))``: a name with no
    ``jit(...)`` head is an argument's. ``None`` or ``""`` -> ``(None, ())``.
    """
    if not op_name:
        return None, ()
    parts = op_name.split("/")
    m = _JIT.match(parts[0])
    if m is None:
        return None, tuple(parts)
    return m.group(1), tuple(parts[1:-1])


def step_of(op_name: Optional[str]) -> str:
    """The plan step an operation belongs to: its outermost scope inside
    the program, ``ARGUMENT`` for one named after the program's argument,
    ``trace.NO_SPAN`` for one with no scope."""
    program, scopes = scope_path(op_name)
    if program is None and scopes:
        return ARGUMENT
    return scopes[0] if scopes else T.NO_SPAN


def is_glue(op_name: Optional[str]) -> bool:
    """Under a ``split`` or ``unsplit`` scope, or the compiler's own
    unpacking of the program's complex argument."""
    program, scopes = scope_path(op_name)
    if program is None:
        return bool(scopes)
    return any(s in GLUE_SCOPES for s in scopes)


def event_kind(name: str) -> str:
    return name.split(" ", 1)[0]


def clip(intervals: Iterable[tuple], start: float, end: float) -> list:
    return [(max(s, start), min(e, end)) for s, e in intervals
            if e > start and s < end]


def within(a: list, b: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += T.overlap(a[i], b[j])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _stat(name: str, groups, stat_names: dict) -> Optional[str]:
    """A string stat of an event, from its own stats or its metadata's;
    a string the plane interns is a reference to a stat metadata entry."""
    for stats in groups:
        for st in stats:
            if stat_names.get(st.metadata_id) == name:
                if st.ref_value:
                    return stat_names.get(st.ref_value)
                return st.str_value
    return None


@functools.lru_cache(maxsize=None)
def _xspace_class():
    """The parts of the profiler's ``XSpace`` protobuf (tsl
    ``profiler/protobuf/xplane.proto``) this module reads, field numbers
    as there."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    pkg = "sarbench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")

    def message(container, name, fields):
        m = container.add(name=name)
        for field, number, kind, of in fields:
            label = F.LABEL_REPEATED if of else F.LABEL_OPTIONAL
            f = m.field.add(name=field, number=number, type=kind,
                            label=label)
            if kind == F.TYPE_MESSAGE:
                f.type_name = f".{pkg}.{of}"
        return m

    msg, i64, txt = F.TYPE_MESSAGE, F.TYPE_INT64, F.TYPE_STRING
    message(fd.message_type, "XSpace", [("planes", 1, msg, "XPlane")])
    plane = message(fd.message_type, "XPlane", [
        ("name", 2, txt, None), ("lines", 3, msg, "XLine"),
        ("event_metadata", 4, msg, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry, [("key", 1, i64, None)])
        e.field.add(name="value", number=2, type=msg,
                    label=F.LABEL_OPTIONAL, type_name=f".{pkg}.{value}")
        e.options.map_entry = True
    message(fd.message_type, "XLine", [
        ("name", 2, txt, None), ("timestamp_ns", 3, i64, None),
        ("events", 4, msg, "XEvent")])
    message(fd.message_type, "XEvent", [
        ("metadata_id", 1, i64, None), ("offset_ps", 2, i64, None),
        ("duration_ps", 3, i64, None), ("stats", 4, msg, "XStat")])
    message(fd.message_type, "XStat", [
        ("metadata_id", 1, i64, None), ("str_value", 5, txt, None),
        ("ref_value", 7, F.TYPE_UINT64, None)])
    message(fd.message_type, "XEventMetadata", [
        ("name", 2, txt, None), ("stats", 5, msg, "XStat")])
    message(fd.message_type, "XStatMetadata", [("name", 2, txt, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _times(line, event) -> tuple:
    """(start, end) of an event: whole ns, as ``jax.profiler.ProfileData``
    gives them."""
    start = float(line.timestamp_ns + event.offset_ps // 1000)
    return start, start + event.duration_ps // 1000


def _device_events(plane) -> tuple:
    """The operations of a chip plane's ``XLA Ops`` line, as
    ``([(instruction name, start, end)], [(op_name or None, start, end)])``.
    """
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    metadata = plane.event_metadata
    ops, named = [], []
    for line in plane.lines:
        if line.name != T.OPS_LINE:
            continue
        for e in line.events:
            start, end = _times(line, e)
            md = metadata[e.metadata_id]
            ops.append((T.op_name(md.name), start, end))
            named.append((_stat(OP_NAME_STAT, (e.stats, md.stats),
                                stat_names), start, end))
    return ops, named


def _host_events(plane, span_names: set) -> tuple:
    """The host plane's spans named in ``span_names`` and its copy work,
    as ``([(name, start, end)], [(kind, thread, start, end)])``."""
    spans, host = [], []
    wanted = set(RELAYOUT) | set(TRANSFER)
    names = {k: v.name for k, v in plane.event_metadata.items()}
    # a host plane holds about 600,000 tile transposes a call: pick the
    # wanted names by metadata id before the events
    span_ids = {k: n for k, n in names.items() if n in span_names}
    kinds = {k: event_kind(n) for k, n in names.items()
             if event_kind(n) in wanted}
    for line in plane.lines:
        for e in line.events:
            if e.metadata_id in span_ids:
                spans.append((span_ids[e.metadata_id], *_times(line, e)))
            elif e.metadata_id in kinds:
                host.append((kinds[e.metadata_id], line.name,
                             *_times(line, e)))
    return spans, host


@dataclasses.dataclass
class ScopedTrace(T.Trace):
    """A ``trace.Trace`` plus each device operation's ``op_name`` and the
    runtime's host events of copy work."""

    named: dict = dataclasses.field(default_factory=dict)
    # chip -> [(op_name or None, start_ns, end_ns)]
    host: list = dataclasses.field(default_factory=list)
    # [(event name, thread, start_ns, end_ns)]

    @classmethod
    def from_xplane(cls, path: str, span_names: set) -> "ScopedTrace":
        """One walk of the profile file: the device operations and host
        spans ``trace.Trace.from_xplane`` keeps, and this class's own
        fields."""
        ops: dict = {}
        named: dict = {}
        spans, host = [], []
        with open(path, "rb") as f:
            space = _xspace_class().FromString(f.read())
        for plane in space.planes:
            if T.is_device_plane(plane.name):
                ops[plane.name], chip_named = _device_events(plane)
                if chip_named:
                    named[plane.name] = chip_named
            elif plane.name == T.HOST_PLANE:
                spans, host = _host_events(plane, span_names)
        return cls.from_events(ops, spans, named=named, host=host)

    # -- device operations by scope -----------------------------------------
    def programs(self) -> set:
        return {scope_path(n)[0] for evs in self.named.values()
                for n, _, _ in evs} - {None}

    def names_steps(self) -> bool:
        """Whether the traced program names its plan steps."""
        return any(p.startswith(PROGRAM_PREFIX) for p in self.programs())

    def _seconds(self, keep) -> float:
        """Union of the window's device operations whose ``op_name``
        ``keep`` accepts, mean over chips."""
        if not self.named:
            return 0.0
        total = sum(T.length(T.union(clip(((s, e) for n, s, e in evs
                                           if keep(n)),
                                          self.start_ns, self.end_ns)))
                    for evs in self.named.values())
        return total / len(self.named) * 1e-9

    def seconds_by_step(self) -> dict:
        steps = {step_of(n) for evs in self.named.values() for n, _, _ in evs}
        out = {k: self._seconds(lambda n, k=k: step_of(n) == k)
               for k in steps}
        return {k: v for k, v in sorted(out.items(), key=lambda kv: -kv[1])
                if v > 0}

    def glue_seconds(self) -> float:
        return self._seconds(is_glue)

    # -- host events inside host spans --------------------------------------
    def has_host_events(self, names: Iterable[str]) -> bool:
        names = set(names)
        return any(n in names for n, _, _, _ in self.host)

    def host_share(self, names: Iterable[str], spans: Iterable[str]) -> float:
        """The union, over every host thread, of the named host events
        inside the union of the named spans, over the length of that
        union (0 where the spans hold no time)."""
        names, spans = set(names), set(spans)
        inside = T.union(clip(((s, e) for n, s, e in self.spans
                               if n in spans), self.start_ns, self.end_ns))
        span_len = T.length(inside)
        if span_len <= 0:
            return 0.0
        events = T.union((s, e) for n, _, s, e in self.host if n in names)
        return within(events, inside) / span_len


# ---------------------------------------------------------------------------
# The profile of a traced run
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> ScopedTrace:
    from sarbench.harness import SPAN_NAMES

    return ScopedTrace.from_xplane(path, SPAN_NAMES)


def for_run(run) -> Optional[ScopedTrace]:
    """The scoped reading of a traced run's profile: the run's own trace
    where it is one, else found under TMPDIR; None where the run was not
    traced or its profile is gone."""
    if run.trace is None or isinstance(run.trace, ScopedTrace):
        return run.trace
    files = glob.glob(os.path.join(tempfile.gettempdir(), RECORDING_GLOB),
                      recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        try:
            st = _load(path, os.path.getmtime(path))
        except (OSError, ValueError):
            continue                      # another run's, or incomplete
        if (st.start_ns, st.end_ns) == (run.trace.start_ns,
                                        run.trace.end_ns):
            return st
    return None
