"""What every kind of cell shares: the look for a chip, the program's
counters, the memory reading, the profiler window."""

import contextlib
import glob
import os
import shutil
import tempfile
import time

from benchmarks import peaks, trace


def find_device(chips, require_tpu=True):
    """What JAX found, as the result line reports it, with the chip's
    peaks.  No TPU, fewer chips than the cell asks for, or a device
    missing from the peaks table raises SystemExit before any work.
    ``require_tpu=False`` is the CPU rehearsal's (benchmarks/tests): it
    drops the platform check and borrows the v5e row, and no number it
    prints is a device number."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips}
    if not require_tpu:
        return device, peaks.PEAKS["TPU v5 lite"]
    if device["platform"] != "tpu":
        raise SystemExit("benchmark: no TPU — jax found platform %r"
                         % device["platform"])
    if len(devs) < chips:
        raise SystemExit("benchmark: the cell needs %d chips, jax found %d"
                         % (chips, len(devs)))
    try:
        return device, peaks.peaks_for(device["kind"])
    except KeyError as e:
        raise SystemExit("benchmark: %s" % e.args[0])


def counter_total(name, labelnames=("event",), **match):
    """Sum over one of the program's telemetry counters."""
    from veles_tpu import telemetry
    inst = telemetry.registry.counter(name, labelnames=labelnames)
    return sum(v for labels, v in inst.samples()
               if all(labels.get(k) == val for k, val in match.items()))


def compile_events():
    return counter_total("veles_compile_events_total")


def memory_peak_bytes(chips=1):
    """Peak bytes in use on the fullest chip, as the device reports."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class Phases:
    """Seconds of a run by phase, for the result line's ``notes``:
    ``mark(name)`` closes the phase that began at the last mark (or at
    the process's start).  Where set-up's time goes is then in every
    run's line, not only in ``setup_s``'s sum."""

    def __init__(self, t0):
        self.last, self.seconds = t0, {}

    def mark(self, name):
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.last
        self.last = now


#: where the CPU rehearsal (benchmarks/tests) finds "device" operations
CPU_TRACE_NAMES = {"device_plane": "/host:CPU", "op_line": "tf_XLAPjRtCpu"}


class TraceWindow:
    """A profiler capture of part of the measured window.  ``start`` /
    ``stop`` bracket it; ``reduce`` reads the ``.xplane.pb`` into the
    numbers ``benchmarks/trace.py`` defines and deletes the files."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t0 = self.t1 = None

    def start(self):
        import jax
        # the Python tracer (a frame per call, on every thread) would
        # slow the host it measures; TraceMe spans and device ops stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, chips=1):
        import jax
        names = {} if jax.default_backend() == "tpu" else CPU_TRACE_NAMES
        try:
            paths = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return trace.reduce(paths[0], chips=chips, **names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def span(name):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def wrap_in_span(obj, method, name):
    """Put a host span round one bound method of one object, from the
    outside: the benchmark's own span at a layer's boundary where the
    program has none yet (choosing-metrics guide, section 4).  Used in
    traced runs only."""
    inner = getattr(obj, method)

    def spanned(*args, **kwargs):
        with span(name):
            return inner(*args, **kwargs)

    setattr(obj, method, spanned)
