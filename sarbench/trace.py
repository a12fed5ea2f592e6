"""The reduction from a profiler trace to the benchmark's numbers.

A traced run records its measured window with ``jax.profiler`` (Python
tracing off). ``Trace.from_xplane`` keeps two kinds of events:

- device operations: the events of the ``XLA Ops`` line of every TPU
  plane (each HLO operation and Pallas kernel as it ran on the chip);
- host spans: the benchmark's own ``TraceAnnotation`` spans on the host
  plane: ``window`` around the whole measured window, which bounds every
  reduction, and ``h2d``, ``dispatch`` and ``d2h`` inside it.

From those:

- busy time is the union of the device operations' intervals, averaged
  over the chips; the idle share is 1 - busy / window;
- the top device operations are summed by name;
- each idle gap of the device is split by the host span open during it,
  and summed by that span's name (``none`` where no span was open).

A run reads its profile once, with ``scopes.ScopedTrace.from_xplane``,
which keeps these events and more; ``Trace.from_xplane`` reads them
through ``jax.profiler.ProfileData``, the reading it is tested against.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Iterable

OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
NO_SPAN = "none"
WINDOW_SPAN = "window"


def is_device_plane(name: str) -> bool:
    """``/device:TPU:<n>``: one chip's plane."""
    return name.startswith(DEVICE_PLANE_PREFIX) and \
        name[len(DEVICE_PLANE_PREFIX):].isdigit()


def op_name(hlo: str) -> str:
    """``%spectral_axis1.1 = (f32[...]) custom-call(...)`` ->
    ``spectral_axis1``: the instruction's name without its number."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list[list[float]], start: float, end: float):
    """Complement of merged ``busy`` within [start, end]."""
    t = start
    for s, e in busy:
        if s > t:
            yield (t, min(s, end))
        t = max(t, e)
        if t >= end:
            return
    if t < end:
        yield (t, end)


def overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass
class Trace:
    """Device operations per chip and host spans, in ns on one clock."""

    ops: dict            # chip -> [(name, start_ns, end_ns)]
    spans: list          # [(name, start_ns, end_ns)]
    start_ns: float
    end_ns: float

    @classmethod
    def from_xplane(cls, path: str, span_names: set) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        ops: dict = {}
        spans = []
        for plane in pd.planes:
            if is_device_plane(plane.name):
                evs = ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend((op_name(e.name), e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events
                                 if e.name in span_names)
        return cls.from_events(ops, spans)

    @classmethod
    def from_events(cls, ops: dict, spans: list, **fields) -> "Trace":
        """The trace bounded by the one ``window`` span among ``spans``;
        ``fields`` are a subclass's own."""
        windows = [s for s in spans if s[0] == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"the trace holds {len(windows)} "
                             f"{WINDOW_SPAN!r} spans, not one")
        _, start, end = windows[0]
        if ops and not any(e > start and s < end
                           for evs in ops.values() for _, s, e in evs):
            raise ValueError("no device operation falls inside the traced "
                             "window: the device and host clocks disagree, "
                             "or nothing ran on the chip")
        return cls(ops, [s for s in spans if s[0] != WINDOW_SPAN],
                   start, end, **fields)

    # -- reductions ----------------------------------------------------------
    def window_seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def _busy(self, chip: str) -> list[list[float]]:
        return union((max(s, self.start_ns), min(e, self.end_ns))
                     for _, s, e in self.ops[chip]
                     if e > self.start_ns and s < self.end_ns)

    def busy_seconds(self) -> float:
        """Union of device operations inside the window, mean over chips.
        Copies between host and chip are no device operations: they run
        on the host (layout transposes) and the DMA engines, so they count
        as idle time of the chip."""
        if not self.ops:
            return 0.0
        return sum(length(self._busy(c)) for c in self.ops) \
            / len(self.ops) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_seconds() / self.window_seconds()

    def top_ops(self, k: int = 10) -> list:
        total: dict = defaultdict(float)
        for evs in self.ops.values():
            for n, s, e in evs:
                total[n] += overlap((s, e), (self.start_ns, self.end_ns))
        n_chips = max(1, len(self.ops))
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / n_chips * 1e-9] for n, t in ranked]

    def idle_by_span(self, k: int = 10) -> list:
        """Device idle time split by the host span open during it: each
        name's share is the union of its spans inside the gaps."""
        total: dict = defaultdict(float)
        spans = sorted(self.spans, key=lambda s: s[1])
        for chip in self.ops:
            for g in gaps(self._busy(chip), self.start_ns, self.end_ns):
                covered: dict = defaultdict(list)
                for name, s, e in spans:
                    if s >= g[1]:
                        break
                    if overlap(g, (s, e)) > 0:
                        covered[name].append((max(s, g[0]), min(e, g[1])))
                for name, ivs in covered.items():   # spans of one name may
                    total[name] += length(union(ivs))   # overlap
                total[NO_SPAN] += (g[1] - g[0]) - length(union(
                    iv for ivs in covered.values() for iv in ivs))
        n_chips = max(1, len(self.ops))
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / n_chips * 1e-9] for n, t in ranked if t > 0]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}


class Recording:
    def __init__(self, directory: str):
        self.directory = directory

    def path(self) -> str:
        """The one profile file the recording wrote."""
        files = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return files[0]


@contextlib.contextmanager
def recording():
    """Profile the enclosed block into a temporary directory (under
    TMPDIR), Python tracing off; the directory goes when the run ends."""
    import atexit

    import jax

    directory = tempfile.mkdtemp(prefix="sarbench_trace_")
    atexit.register(shutil.rmtree, directory, True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield Recording(directory)
    finally:
        jax.profiler.stop_trace()
