"""The benchmark's own spans and the capture of a profiler trace.

Spans are `jax.profiler.TraceAnnotation`s named `bench:<name>`, so they land
in the profiler's trace on the device's clock and an idle gap can be charged
to what the harness was doing. The trace is written under `.bench_tmp/` in
the checkout, reduced at once (trace_reduce.summarize) and deleted.
"""
import os
import shutil
import time

from . import trace_reduce


def span(name):
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


def capture(fn, trace_dir, n_devices=1, keep_copy=None):
    """Run `fn()` under the profiler; returns the reduced trace (None where
    the trace holds no device operation, as on the CPU) with the host-clock
    seconds the capture took."""
    import jax
    from jax.profiler import ProfileData
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    host_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    path = trace_reduce.find_xplane(trace_dir)
    size = os.path.getsize(path)
    summary = trace_reduce.summarize(ProfileData.from_file(path), n_devices)
    if summary is not None:
        summary["host_s"] = host_s
        summary["reduce_s"] = time.perf_counter() - t1
        summary["xplane_bytes"] = size
    if keep_copy:
        os.makedirs(keep_copy, exist_ok=True)
        shutil.copy(path, keep_copy)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


class CompileCounter:
    """Every request jax makes of its compilation cache, hit or miss: one per
    program compiled or loaded. None may come inside the measured window."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def requests(self):
        return self.hits + self.misses
