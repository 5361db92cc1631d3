"""Instrumentation the benchmark puts around the program from outside.

- ``SpanBridge``: a ``repro.mining.telemetry.TraceRecorder`` whose scoped
  spans also open a ``jax.profiler.TraceAnnotation``, so the program's
  own spans (``group.prep``, ``mine.wave``, ``mine.reduce``,
  ``stream.append`` ...) land on the profiler's clock beside the device
  ops, where idle gaps can be attributed to them.
- ``client_span``: the same for the client's own steps.
- ``CompileCounter``: counts JAX traces and backend compiles, so a run can
  show that nothing compiled inside its measured window.
- ``GcPauses``: times the collections of Python's oldest generation in the
  window, which stop every thread of the process.
- ``Profiler``: the JAX profiler around the window, host Python tracing
  off, into a fixed directory of the checkout.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import os
import shutil
import time

import jax

HOST_PREFIX = "bench:"  # every annotation the benchmark writes starts so
WINDOW_ANNOTATION = HOST_PREFIX + "window"  # spans the measured window

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


def client_span(name: str):
    """A profiler annotation for one of the client's own steps."""
    return jax.profiler.TraceAnnotation(f"{HOST_PREFIX}client.{name}")


def span_bridge():
    """A TraceRecorder whose scoped spans are also profiler annotations."""
    from repro.mining.telemetry import TraceRecorder

    class SpanBridge(TraceRecorder):
        @contextlib.contextmanager
        def span(self, name, *, parent=None, **args):
            with jax.profiler.TraceAnnotation(f"{HOST_PREFIX}{name}"):
                with super().span(name, parent=parent, **args) as sid:
                    yield sid

    return SpanBridge()


class CompileCounter:
    """Counts traces and backend compiles from JAX's monitoring events
    while ``counting`` is set."""

    def __init__(self):
        self.counting = False
        self.count = 0
        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.counting and event in _COMPILE_EVENTS:
            self.count += 1

    def close(self) -> None:
        self.counting = False
        jax.monitoring.unregister_event_duration_listener(self._listener)


class GcPauses:
    """Times the collections of Python's oldest generation until closed."""

    def __init__(self):
        self.count, self.total_s, self.longest_s = 0, 0.0, 0.0
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self.count, self.total_s = self.count + 1, self.total_s + dt
            self.longest_s = max(self.longest_s, dt)
            self._t0 = None

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


class Profiler:
    """``jax.profiler`` into ``directory`` (emptied first), no Python
    tracer. ``xplane()`` is the written trace file."""

    def __init__(self, directory: str):
        self.directory = directory
        self.running = False

    def start(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.running = True

    def stop(self) -> None:
        if self.running:
            self.running = False
            jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no profiler trace under {self.directory}")
        return found[-1]
