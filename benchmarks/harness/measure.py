"""What every driver measures with: the benchmark's own host spans, the
measured window, and the context and outcome of one run."""

import contextlib
import dataclasses
import time

import numpy as np


def span_total(spans, *names):
    """Summed seconds of the spans with one of those names; None if there is
    none.  Reads the benchmark's spans and the program's alike."""
    durs = [s["dur_s"] for s in spans if s["name"] in names]
    return sum(durs) if durs else None


class Spans:
    """Host spans recorded by the benchmark around its calls into the program.
    Each is also a `jax.profiler.TraceAnnotation`, so a profiler trace carries
    it on the device trace's own clock as `bench:<name>`."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.done = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench:" + name):
                yield
        finally:
            self.done.append({"name": name, "start_s": t0 - self.t_start,
                              "dur_s": time.perf_counter() - t0})


class Window:
    """The measured window: laps of work, each of `units` units, taken until
    `seconds` have passed.  A lap that failed counts as attempted and has no
    time."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.laps = []  # seconds per unit, one entry per good lap
        self.units = 0
        self.attempted = 0
        self.failed = 0
        self.t0 = None

    def start(self) -> None:
        self.t0 = self._t_lap = time.perf_counter()

    @property
    def over(self) -> bool:
        return time.perf_counter() - self.t0 >= self.seconds

    def lap(self, units: int, ok: bool = True) -> None:
        now = time.perf_counter()
        self.attempted += 1
        if ok:
            self.laps.append((now - self._t_lap) / units)
            self.units += units
        else:
            self.failed += 1
        self._t_lap = now

    def percentile(self, q: float) -> float:
        return float(np.percentile(self.laps, q))


@dataclasses.dataclass
class Run:
    """One invocation: the cell, its two files, the arguments, the clocks."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    device: dict
    spans: Spans
    cache_dir: str

    @property
    def setup_s(self) -> float:
        return time.perf_counter() - self.spans.t_start


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.  `values` holds the end-to-end metrics by
    name; `record` is what the per-layer readers read (traced runs only);
    `checks` are (what, passed) pairs, all of which make `correct`;
    `memory_peak_bytes` is the peak on the fullest chip; `notes` go on a
    labelled line before the result."""

    values: dict
    attempted: int
    failed: int
    checks: list
    memory_peak_bytes: int
    record: dict = None
    notes: dict = dataclasses.field(default_factory=dict)
