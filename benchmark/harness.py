"""One run of one cell: set-up, the measured window, the checks, and the
numbers the result line is built from.

A driver (benchmark/drivers/<kind>.py) gets a Run. It builds its inputs,
warms its shapes, then measures inside `with run.window():` for exactly
`run.seconds`. Everything before the window is set-up. Around its calls
into the system it opens harness spans (`run.span(name)`), which go into
the profiler trace as "bench/<name>" annotations when the run is traced
and whose durations inside the window the per-layer readers see. After the
window it records the comparisons that decide `correct` with `run.check`.
"""

import contextlib
import shutil
import sys
import tempfile
import threading
import time

from . import trace as trace_mod


class Run:
    def __init__(self, cell, cfg, traffic, seed, seconds, traced, t0,
                 control=None, backend_factory=None):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.t0 = t0
        self.control = control
        self._backend_factory = backend_factory
        self.checks = []  # (name, value, limit); correct iff value <= limit
        self.e2e = {}  # end-to-end metric name -> value, from the driver
        self.counts = {}  # what the per-layer readers divide by
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.window_start = self.window_end = None
        self.window_s = None  # the window's length as it ran
        self.span_s = {}  # span name -> [seconds] inside the window
        self.hist = {}  # program histogram name -> [samples] in the window
        self.counters = {}  # program counter deltas over the window
        self.window_compiles = 0
        self.reduced = None  # trace.reduce() of the traced window
        self.memory_peak = None
        self._lock = threading.Lock()
        self._in_window = False
        self._compiles = 0
        self._closed_at = None
        self.jax_setup_s = {}  # JAX duration event -> seconds in set-up

    # -- the system under test -------------------------------------------

    def backend(self):
        """The device backend the window drives (tests pass another)."""
        if self._backend_factory is not None:
            return self._backend_factory()
        from coconut_tpu.tpu.backend import JaxBackend

        return JaxBackend()

    # -- spans -------------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name):
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)
        else:
            ann = contextlib.nullcontext()
        t = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            dt = time.perf_counter() - t
            if self._in_window:
                with self._lock:
                    self.span_s.setdefault(name, []).append(dt)

    def wrap(self, obj, attr, name):
        """Time every call of obj.<attr> under span `name` (on this
        instance only: the program's code is not changed)."""
        fn = getattr(obj, attr)

        def timed(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)

        setattr(obj, attr, timed)
        return fn

    # -- the window ---------------------------------------------------------

    def mark(self, what):
        """Log how far into set-up the run is (process start to now)."""
        log("setup %s at_s=%.3f" % (what, time.perf_counter() - self.t0))

    def close(self):
        """Mark the window's end now (the driver may still be unwinding
        when the `with run.window()` block exits)."""
        if self._closed_at is None:
            self._closed_at = time.perf_counter()

    def remaining(self):
        return self.window_end - time.perf_counter()

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; the profiler
        (with --trace 1) and the program-counter deltas cover it."""
        from coconut_tpu import metrics

        before = dict(metrics.snapshot()["counters"])
        observe = metrics.observe

        def tee(name, seconds):
            observe(name, seconds)
            if self._in_window:
                with self._lock:
                    self.hist.setdefault(name, []).append(seconds)

        metrics.observe = tee
        trace_dir = None
        if self.traced:
            import jax

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_mod.profiler_options()
            )
            ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        else:
            ann = contextlib.nullcontext()
        compiles0 = self._compiles
        start = time.perf_counter()
        self.setup_s = start - self.t0
        for name, secs in sorted(self.jax_setup_s.items(), key=lambda kv: -kv[1]):
            if secs >= 0.1:
                log("setup jax %s total_s=%.3f" % (name, secs))
        self.window_start, self.window_end = start, start + self.seconds
        self._in_window = True
        self._closed_at = None
        try:
            with ann:
                yield self
        finally:
            self._in_window = False
            self.close()
            self.window_s = self._closed_at - start
            self.window_compiles = self._compiles - compiles0
            self.memory_peak = memory_peak(self.cell["chips"])
            metrics.observe = observe
            after = metrics.snapshot()["counters"]
            self.counters = {
                k: v - before.get(k, 0)
                for k, v in after.items()
                if v != before.get(k, 0)
            }
            if trace_dir is not None:
                import jax

                jax.profiler.stop_trace()
                t = time.perf_counter()
                try:
                    events = trace_mod.load_events(
                        trace_mod.find_xplane(trace_dir)
                    )
                    self.reduced = trace_mod.reduce(events)
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                log("trace events=%d reduce_s=%.3f"
                    % (len(events), time.perf_counter() - t))
                for m, secs in sorted(self.reduced["module_s"].items()):
                    log("trace program %s device_s=%.6f runs=%d"
                        % (m, secs, self.reduced["module_runs"][m]))

    def listen(self):
        """Count compiles, and total JAX's own set-up durations (tracing,
        lowering, compiling or loading from the cache) per event name,
        for the set-up split the run logs when its window opens."""
        import jax

        def listener(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self._compiles += 1
            if not self._in_window and self.setup_s is None:
                self.jax_setup_s[name] = self.jax_setup_s.get(name, 0.0) + secs

        jax.monitoring.register_event_duration_secs_listener(listener)

    # -- correctness ----------------------------------------------------------

    def check(self, name, value, limit):
        self.checks.append((name, value, limit))

    def correct(self):
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)

    def report_checks(self, stream=None):
        stream = stream or sys.stderr
        for name, value, limit in self.checks:
            print("check %s=%s limit=%s" % (name, value, limit), file=stream)
        stream.flush()


def memory_peak(chips):
    """Peak bytes in use on the fullest of the cell's devices."""
    import jax

    peaks = [0]
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def scratch_dir():
    """A private directory under TMPDIR for a run's transient files."""
    return tempfile.mkdtemp(prefix="bench-")
