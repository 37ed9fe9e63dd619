"""The benchmark's own spans and its traced window.

A :class:`Recorder` keeps every span in memory on the host's clock
(``time.perf_counter``) and, in a traced run, also writes it into the
profiler's trace as a ``jax.profiler.TraceAnnotation`` — which puts it on
the device's clock beside the device operations. A :class:`WindowTracer`
opens the profiler for a short stretch inside the measured window."""

from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"


class Recorder:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: List[Tuple[str, float, float, dict]] = []
        self.t0: Optional[float] = None  # the window's start, set by the runner

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name, **attrs):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter(), attrs))


class WindowTracer:
    """The profiler around a traced run's whole window. It is opened before
    the window does and stopped after the drain, because starting and
    stopping it each stall the host for a second or two — inside the window
    that would be a queue of its own making. The ``chipbench.window`` span
    marks the measured window itself."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.window_s: Optional[float] = None
        self.path: Optional[str] = None
        self._span = None
        self._t_open = None

    def open(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._t_open = time.perf_counter()

    def mark_end(self) -> None:
        """The measured window has closed (the profiler runs on)."""
        if self._span is not None:
            self.window_s = time.perf_counter() - self._t_open
            self._span.__exit__(None, None, None)
            self._span = None

    def close(self) -> None:
        import jax

        self.mark_end()
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.path = found[-1] if found else None
