"""The measured window: timing, compile guard and the optional trace.

A driver sets up everything first, then runs its loop inside one
``Window``.  The window starts the profiler (``--trace 1``) before its
clock starts, marks itself with the host span ``bench.window`` that the
trace reduction clips to, and raises if anything compiled (or was
loaded from the compilation cache) while it was open.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Any

WINDOW_SPAN = "bench.window"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileInWindow(RuntimeError):
    """A program compiled inside the measured window."""


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``.

    ``end_to_end`` maps metric names to values; ``counts`` holds what the
    per-layer readers divide by; ``checks`` is a list of
    ``(name, value, limit)``, each correct when ``value <= limit``.
    """

    end_to_end: dict[str, float]
    counts: dict[str, Any]
    checks: list[tuple[str, float, float]]
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Any = None          # xplane.Reduced of a --trace 1 run


def span(name: str):
    """A host span in the profiler trace (cheap when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 if unknown)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


class CompileCounter:
    """Counts executables compiled or loaded while it is entered."""

    def __init__(self):
        self.count = 0
        self.names: list[str] = []

    def _listen(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


class Window:
    """``with Window(seconds, trace) as w: while w.open(): ...``

    ``elapsed`` is from the start to the end of the last unit of work
    begun before the deadline; ``reduced`` is the reduced trace.
    """

    def __init__(self, seconds: float, trace: bool):
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.elapsed = 0.0
        self.reduced = None
        self._dir = None
        self._compiles = CompileCounter()
        self._span = None

    def open(self) -> bool:
        return time.perf_counter() < self.deadline

    def __enter__(self):
        import jax
        if self.trace:
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._compiles.__enter__()
        self._span = span(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
        self.elapsed = time.perf_counter() - self.t0
        self._span.__exit__(None, None, None)
        self._compiles.__exit__()
        try:
            if self.trace:
                jax.profiler.stop_trace()
            if self.trace and exc_type is None:
                from . import xplane
                self.reduced = xplane.reduce_dir(self._dir)
        finally:
            if self._dir:
                shutil.rmtree(self._dir, ignore_errors=True)
        if exc_type is None and self._compiles.count:
            raise CompileInWindow(
                f"{self._compiles.count} program(s) compiled inside the "
                f"measured window: {sorted(set(self._compiles.names))}")
        return False
