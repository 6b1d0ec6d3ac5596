"""The traced window of a `--trace 1` run: the JAX profiler and the program's
own span tracer switched on around a short steady stretch of work, and both
reduced to the record the per-layer readers read."""

import json
import os
import shutil
import threading
import time

from . import xplane_reduce


class HbmSampler:
    """The largest `bytes_in_use` any local device shows while it runs, read
    from a thread every PERIOD_S.  `peak_bytes_in_use` cannot say what the
    window holds: it never falls, and set-up's one-off temporaries set it."""

    PERIOD_S = 0.05  # a chunk program runs for seconds and holds its temporaries throughout

    def __init__(self):
        self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _sample(self) -> None:
        import jax

        devices = jax.local_devices()
        while not self._stop.wait(self.PERIOD_S):
            in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
            self.peak = max(self.peak, *in_use)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


class TraceWindow:
    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.program_jsonl = os.path.join(trace_dir, "program.jsonl")
        self.window_s = None
        self.hbm = HbmSampler()

    def start(self) -> None:
        import jax

        from lightgbm_tpu.obs import tracer

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        # the program's traced-phases mode times another program (one dispatch
        # per split); its default is on only under the interpreter
        os.environ["LIGHTGBM_TPU_TRACE_PHASES"] = "0"
        os.environ["LIGHTGBM_TPU_TRACE"] = self.program_jsonl
        tracer.refresh_from_env()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device ops and annotations only
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(xplane_reduce.WINDOW)
        self._annotation.__enter__()
        self.hbm.start()
        self._wall0 = time.time()
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        from lightgbm_tpu.obs import tracer

        self.window_s = time.perf_counter() - self._t0
        self.hbm.stop()
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        tracer.close()
        del os.environ["LIGHTGBM_TPU_TRACE"]

    def program_spans(self) -> list:
        """The program's spans inside the window: name, duration and the
        seconds from the window's start at which each began."""
        spans = []
        with open(self.program_jsonl) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("ev") == "span":
                    rec["start_s"] = rec["ts"] - rec["dur_s"] - self._wall0
                    spans.append(rec)
        return spans

    def reduce(self, on_device: bool) -> dict:
        """{"program_spans": [...], "window_hbm_bytes": sampled, "device":
        reduced trace or None}.  Off the chip (a rehearsal) there is no device
        plane and `device` is None."""
        spans = self.program_spans()
        if not on_device:
            return {"program_spans": spans, "window_hbm_bytes": self.hbm.peak, "device": None}
        trace = xplane_reduce.read(xplane_reduce.find_xplane(self.dir))
        win = [h for h in trace.host if h.name == xplane_reduce.WINDOW]
        if not win:
            raise RuntimeError(f"the trace under {self.dir} has no {xplane_reduce.WINDOW!r} annotation")
        extra = [xplane_reduce.Event(s["name"], win[0].start + s["start_s"],
                                     win[0].start + s["start_s"] + s["dur_s"])
                 for s in spans]
        return {"program_spans": spans, "window_hbm_bytes": self.hbm.peak,
                "device": xplane_reduce.reduce(trace, extra)}
