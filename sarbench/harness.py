"""One run of one cell: set up, measure a window, check, report.

The loop is closed, with one caller. Each call places a host batch of
``batch`` echoes on the device (span ``h2d``), runs the program's jitted
pipeline on it (span ``dispatch``) and copies the images back to the host
(span ``d2h``), in that order, before the next call. ``scenes_per_s`` is
every scene whose image reached the host over the whole window.

The echoes are made from the seed, and every shape the window uses is
warmed before it. After the window a sample of the images drawn from the
seed is compared with the plain reference (``reference.py``). The number
compared is the worst relative L2 error of an image against the reference
image of its own echo; its limit is in the configuration file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import resource
import sys
import time
from typing import Callable, Optional

import numpy as np

from sarbench import quality, scene as scenelib, work
from sarbench.spec import Cell

# calls whose images are compared, drawn from the seed
SAMPLE_CALLS = 3
# calls a traced window holds at most: the profile grows with every call
TRACED_CALLS = 16
# the compared error of an image holding NaN or Inf (JSON has no Inf)
NON_FINITE = 1e30


def log(tag: str, msg: str) -> None:
    print(f"{tag} {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float


class Spans:
    """Host spans on the host clock, mirrored into the profiler's trace."""

    def __init__(self):
        self.items: list[Span] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append(Span(name, t0, time.perf_counter()))

    def total(self, *names: str) -> float:
        return sum(s.t1 - s.t0 for s in self.items if s.name in names)


@dataclasses.dataclass
class Window:
    """What the window measured, for the end-to-end and per-layer readers."""

    seconds: float                      # length of the measured window
    calls: int                          # calls made in the window
    attempted: int
    failed: int
    scenes: int                         # images that reached the host
    e2e: dict                           # end-to-end metric name -> value
    spans: Spans
    compiles: int = 0                   # backend compiles inside the window


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""

    cell: Cell
    scene: scenelib.Scene
    peak: dict
    window: Window
    trace: object = None                # scopes.ScopedTrace, or None

    @property
    def spans(self) -> Spans:
        return self.window.spans


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def program_scene(config: dict):
    """The program's SceneConfig for a configuration file."""
    from repro.core.sar import SceneConfig

    return SceneConfig(**{k: config["scene"][k] for k in scenelib.SCENE_KEYS})


def pipeline_entry(config: dict) -> Callable:
    """The timed entry: the program's jitted pipeline."""
    from repro.core.sar import build_pipeline

    return build_pipeline(program_scene(config), config["variant"],
                          precision=config["precision"]).jitted()


# ---------------------------------------------------------------------------
# Compile counting
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA backend compiles (not persistent-cache hits)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# Loops
# ---------------------------------------------------------------------------

class ClosedLoop:
    def __init__(self, cell: Cell, scene: scenelib.Scene, seed: int,
                 entry_factory: Callable = pipeline_entry):
        t = cell.traffic
        self.cell, self.scene, self.seed = cell, scene, seed
        self.batch = int(t["batch"])
        self.n_echoes = int(t["echoes"])
        if self.n_echoes % self.batch:
            raise ValueError("echoes must be a multiple of batch")
        self.entry_factory = entry_factory
        self.samples: list = []        # (host batch index, images)

    def setup(self) -> None:
        import jax

        targets = scenelib.targets_from_config(self.cell.config, self.scene)
        echoes = scenelib.make_echoes(self.scene, targets, self.seed,
                                      self.n_echoes)
        self.host_batches = [np.stack(echoes[i:i + self.batch])
                             for i in range(0, self.n_echoes, self.batch)]
        del echoes
        self.entry = self.entry_factory(self.cell.config)
        # every host batch has one shape: one call compiles (or loads)
        # all the window runs
        np.asarray(jax.block_until_ready(
            self.entry(jax.device_put(self.host_batches[0]))))

    def run(self, seconds: float, counter: CompileCounter,
            max_calls: Optional[int] = None) -> Window:
        """Measure until ``seconds`` have passed or, where given,
        ``max_calls`` calls have been made, whichever comes first."""
        rng = np.random.default_rng([self.seed, 1])
        spans = Spans()
        c0 = counter.count
        with spans("window"):
            t_start = time.perf_counter()
            calls = self._calls(seconds, spans, rng, max_calls)
        window = spans.items[-1].t1 - t_start
        scenes = calls * self.batch
        return Window(window, calls, scenes, 0, scenes,
                      {"scenes_per_s": scenes / window}, spans,
                      compiles=counter.count - c0)

    def _calls(self, seconds: float, spans: Spans, rng,
               max_calls: Optional[int]) -> int:
        import jax

        n_hb = len(self.host_batches)
        calls = 0
        t_start = time.perf_counter()
        while True:
            k = calls % n_hb
            with spans("h2d"):
                x = jax.block_until_ready(jax.device_put(self.host_batches[k]))
            with spans("dispatch"):
                y = jax.block_until_ready(self.entry(x))
            with spans("d2h"):
                images = np.asarray(y)
            del x, y
            # reservoir sample of calls, drawn from the seed
            if len(self.samples) < SAMPLE_CALLS:
                self.samples.append((k, images))
            else:
                j = int(rng.integers(0, calls + 1))
                if j < SAMPLE_CALLS:
                    self.samples[j] = (k, images)
            calls += 1
            if time.perf_counter() - t_start >= seconds or calls == max_calls:
                return calls

    def compared(self):
        """(echo key, echo, image) for each image to compare."""
        for k, images in self.samples:
            for row in range(self.batch):
                yield (k, row), self.host_batches[k][row], images[row]

    def close(self) -> None:
        self.entry = None


SPAN_NAMES = {"window", "h2d", "dispatch", "d2h"}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def compare(loop, scene: scenelib.Scene, config: dict, tag: str) -> dict:
    """Compare the sampled images with the reference images of their own
    echoes. The compared number is the worst relative L2 error of a whole
    image; its profile over eight bands of range columns and the point
    targets of the first image are logged beside it."""
    import jax

    from sarbench.reference import Reference

    ref = Reference(scene)
    cache: dict = {}
    worst = 0.0
    profile = np.zeros(8)
    first = None
    n = 0
    for key, echo, image in loop.compared():
        if key not in cache:
            cache[key] = np.asarray(ref(jax.device_put(echo)))
        r = cache[key]
        image = np.asarray(image)
        num, den = quality.column_energies(image, r)
        err = quality.l2_from(num, den)
        # a non-finite image reads as a huge, still finite, error
        worst = max(worst, err if np.isfinite(err) else NON_FINITE)
        profile = np.maximum(profile, quality.band_profile(num, den, 8))
        n += 1
        if first is None:
            first = (image, r)
    log(tag, f"diagnostic: {n} images compared; worst L2 by range band "
             f"{[float(f'{x:.3e}') for x in profile]}")
    if first is not None:
        targets = scenelib.targets_from_config(config, scene)
        moved, dsnr = quality.target_diff(first[0], first[1], scene, targets)
        log(tag, f"diagnostic: first compared image: {moved} target peaks "
                 f"moved, largest SNR change {dsnr:.4f} dB")
    limit = config["check"]["l2_rel_limit"]
    return {"images": n, "l2_rel": worst, "limit": limit,
            "profile": profile.tolist(),
            "correct": n > 0 and limit is not None and worst <= limit}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def memory_peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             tag: str, t_process: float, devices: list, peak: dict,
             factory: Optional[Callable] = None) -> dict:
    """Set up, measure, check; return the result line as a dict.

    ``t_process`` is the host clock (``time.perf_counter``) at process
    start, so ``setup_s`` counts loading JAX and reaching the chip too.
    ``factory`` replaces the system under test (tests, the control)."""
    from sarbench import scopes, trace as tracelib

    scene = scenelib.scene_from_config(cell.config)
    if cell.traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {cell.traffic['loop']!r}")
    loop = ClosedLoop(cell, scene, seed, *(() if factory is None
                                           else (factory,)))
    counter = CompileCounter()
    tr = None
    try:
        loop.setup()
        setup_s = time.perf_counter() - t_process
        log(tag, f"set-up {setup_s:.3f} s; window of {seconds} s "
                 f"{f'or {TRACED_CALLS} calls ' if trace else ''}starts")
        if trace:
            with tracelib.recording() as rec:
                w = loop.run(seconds, counter, max_calls=TRACED_CALLS)
            tr = scopes.ScopedTrace.from_xplane(rec.path(), SPAN_NAMES)
        else:
            w = loop.run(seconds, counter)
        mem = memory_peak_bytes(devices[0])
    finally:
        loop.close()            # the program's state goes before the check
    log(tag, f"window {w.seconds:.3f} s, {w.calls} calls: "
             f"{w.attempted} attempted, "
             f"{w.failed} failed, {w.scenes} images on the host, "
             f"{w.compiles} compiles inside the window")
    check = compare(loop, scene, cell.config, tag)
    run = Run(cell, scene, peak, w, tr)
    metrics = {}
    if not trace:
        values = dict(w.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if m.name not in values:
                raise KeyError(f"the loop does not measure {m.name!r}")
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    else:
        for m, read in cell.per_layer:
            v = read(run)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result = {"correct": bool(check["correct"] and w.failed == 0),
              "attempted": w.attempted, "failed": w.failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_seconds()
        device["window_s"] = tr.window_seconds()
        result["breakdown"] = tr.breakdown()
    result["diagnostics"] = {
        "l2_rel_by_range_band": check["profile"],
        "images_compared": check["images"],
        "compiles_in_window": w.compiles,
        "window_s": w.seconds, "setup_s": setup_s,
        # after the readers, which hold the parsed profile
        "host_rss_peak_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    if trace:
        result["diagnostics"]["traced_calls"] = w.calls
    result["compared"] = {
        "l2_rel": {"value": check["l2_rel"], "limit": check["limit"]},
        "failed": {"value": w.failed, "limit": 0}}
    for name, c in result["compared"].items():
        log(tag, f"compared {name} {c['value']!r} limit {c['limit']!r}")
    return result


def accelerator(cell: Cell):
    """(devices, tag, peaks) for the cell's chips, or None where JAX finds
    no accelerator or too few of them. Turns on caching of every compile
    in the persistent cache (the directory is set before JAX loads)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    d = devices[0]
    tag = f"[{d.platform} {d.device_kind} x{len(devices)}]"
    if d.platform == "cpu":
        log(tag, "JAX found no accelerator; no result")
        return None
    if len(devices) < cell.chips:
        log(tag, f"the cell asks for {cell.chips} chips; no result")
        return None
    return devices[:cell.chips], tag, work.peaks(d.device_kind)


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse
    import json

    from sarbench import spec

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_process = time.perf_counter() if t_process is None else t_process
    cell = spec.load_cell(args.workload)
    found = accelerator(cell)
    if found is None:
        return 3
    devices, tag, peak = found
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      tag=tag, t_process=t_process, devices=devices,
                      peak=peak)
    print(json.dumps(result), flush=True)
    return 0
