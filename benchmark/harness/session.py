"""What every run needs round its driver: the clock that starts with the
process, the device it insists on, the compilation cache at a fixed path
inside the checkout, a count of compilations, the profiler for the
traced slice of the window, and the host spans the benchmark puts round
its own calls into the program.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

T_PROCESS = time.perf_counter()      # (roughly) when the process started

from .manifest import ROOT  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SCRATCH = os.path.join(ROOT, ".bench_out")     # traces, losses; ignored


def say(msg: str) -> None:
    """An earlier line of the output; the last line is the result."""
    print(msg, flush=True)


def prepare_environment(rehearse: bool) -> None:
    """Before JAX is imported. Kernel blocks come from the tracked
    ``autotune_cache.json`` and are never measured here (the program's
    default would time candidates inside the run and rewrite that file),
    so what runs is a function of the committed tree."""
    os.environ["PADDLE_TPU_AUTOTUNE"] = "cached"
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where the machine sets it, else a
    fixed directory in the checkout: the path is part of the cache's key.
    Every program is cached, however quick its compile."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or CACHE_DIR


def require_device(chips: int, rehearse: bool):
    """The devices the cell asks for, or no run: off the chip the
    benchmark fails and prints no result."""
    import jax

    devices = jax.devices()
    if rehearse:
        return devices[:chips]
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.exit(f"benchmark: this cell needs {chips} TPU chip(s); JAX "
                 f"found {len(devices)} x {devices[0].platform} "
                 f"({devices[0].device_kind}). There is no off-chip mode "
                 f"(--rehearse is a CPU rehearsal, not a measurement).")
    return devices[:chips]


class CompileCount:
    """Compilations and cache hits JAX reports, by ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiled = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        # a cache hit also reports a backend-compile duration
        return self.compiled - self.hits, self.hits


def span(name: str):
    """A host span in the profiler's own trace (nothing when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """The profiler over one slice of the window: drivers call
    ``tick(t)`` with the window's clock between their own calls, and it
    starts at ``offset_s`` and stops ``seconds`` later. Off (``--trace
    0``) every call is a comparison and nothing else."""

    def __init__(self, on: bool, spec: dict, window_s: float, tag: str):
        self.start_at = min(spec["offset_s"], max(0.0, window_s / 4))
        self.stop_at = self.start_at + min(spec["seconds"],
                                           max(0.5, window_s / 2))
        self.dir = os.path.join(SCRATCH, "trace", tag)
        self.state = "off" if not on else "waiting"
        self.t_start = self.t_stop = None
        self._window = None

    @property
    def tracing(self) -> bool:
        return self.state == "tracing"

    def tick(self, t: float) -> None:
        if self.state == "waiting" and t >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = span("bench.trace_window")
            self._window.__enter__()
            self.state, self.t_start = "tracing", t
        elif self.state == "tracing" and t >= self.stop_at:
            self.stop(t)

    def stop(self, t: float) -> None:
        if self.state != "tracing":
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state, self.t_stop = "done", t

    def xplane(self):
        if self.state != "done":
            return None
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None
