"""One run of one cell: the clocks, the measured window, the benchmark's
own spans and — with ``--trace 1`` — the profiler around the window.

The benchmark starts ``jax.profiler`` itself (Python tracer off: the
decode loop is host-bound and a per-call tracer would measure itself)
and puts a ``jax.profiler.TraceAnnotation`` around each of its calls
into a layer, so host spans and device operations share the profiler's
clock.  Spans the program records on its own clock (``TRACER``, the
request phases) are shifted onto that clock with the offset read off
the ``znbench.window`` annotation, which both clocks saw.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time

from . import programs

WINDOW_SPAN = "znbench.window"


class Context:
    """What a driver gets: the cell, the seed, the window and the
    spans.  A driver does its set-up, calls :meth:`open_window`, runs
    for ``seconds``, calls :meth:`close_window` and returns a
    :class:`~znbench.harness.result.Outcome`."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 toy: bool, devices: list, t_start: float,
                 scratch: str, keep_trace: str | None = None) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.toy = bool(toy)
        self.devices = devices
        self.t_start = t_start            # perf_counter at process start
        self.scratch = scratch            # removed before exit
        self.keep_trace = keep_trace
        self.setup_s: float | None = None
        self.t_open: float | None = None
        self.t_close: float | None = None
        #: the benchmark's own spans: (name, t0, t1) on perf_counter
        self.spans: list[tuple[str, float, float]] = []
        self.counters: dict = {}
        self._before: dict = {}
        self._tracer_mark = 0
        self._window_cm = None
        self.xplane: str | None = None

    def mark(self, what: str) -> None:
        """A line in the log: how far set-up has come."""
        print(f"znbench: +{time.perf_counter() - self.t_start:7.2f}s "
              f"{what}", flush=True)

    # -- spans ---------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into a layer.  Recorded only in a
        traced run; the untraced run pays one branch."""
        if not self.trace:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    # -- the window ----------------------------------------------------
    def open_window(self) -> None:
        """Set-up ends here: everything before is ``setup_s``."""
        from znicz_tpu.observe import tracing
        if self.trace:
            import jax
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            self._trace_dir = os.path.join(self.scratch, "trace")
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=options)
            self._window_cm = self.span(WINDOW_SPAN)
            self._window_cm.__enter__()
        self._tracer_mark = tracing.TRACER.mark()
        self._before = programs.snapshot()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start

    def close_window(self) -> None:
        self.t_close = time.perf_counter()
        self.counters = programs.delta(self._before,
                                       programs.snapshot())
        if self.trace:
            import jax
            self._window_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
            hits = glob.glob(os.path.join(self._trace_dir, "**",
                                          "*.xplane.pb"),
                             recursive=True)
            if not hits:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            self.xplane = max(hits, key=os.path.getmtime)
            if self.keep_trace:
                os.makedirs(self.keep_trace, exist_ok=True)
                shutil.copy(self.xplane, os.path.join(
                    self.keep_trace, f"{self.cell.name}.xplane.pb"))

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    # -- the program's own spans ---------------------------------------
    def program_spans(self) -> list[dict]:
        """Complete spans the program's ``TRACER`` recorded inside the
        window, with ``t0``/``t1`` on this process's ``perf_counter``
        (the tracer stamps microseconds since its own epoch)."""
        from znicz_tpu.observe import tracing
        shift = time.perf_counter() - tracing.now_us() / 1e6
        out = []
        for ev in tracing.TRACER.to_chrome_trace(
                since=self._tracer_mark)["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            t0 = ev["ts"] / 1e6 + shift
            out.append({"name": ev["name"], "cat": ev.get("cat", ""),
                        "t0": t0, "t1": t0 + ev.get("dur", 0.0) / 1e6,
                        "args": ev.get("args", {})})
        return out
