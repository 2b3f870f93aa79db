"""Opens and closes the benchmark's own profiler window."""

import shutil
import tempfile

from benchmarks.harness import trace as trace_lib


class ProfilerWindow:
    """``start()`` .. ``stop()`` around a few seconds of the measured
    window; ``summary()`` reduces the trace and deletes it. The python
    tracer is off (it slows the host); host ``TraceAnnotation``s stay."""

    SPAN = "traced"

    def __init__(self, chips: int):
        self.chips = chips
        self.dir = None
        self._span = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(
            trace_lib.HOST_PREFIX + self.SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self):
        try:
            devices, host = trace_lib.load(trace_lib.find_xplane(self.dir))
            window = trace_lib.window_of(host, self.SPAN)
            host = [s for s in host if s[0] != self.SPAN]
            return trace_lib.TraceSummary(devices, host, window, self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock, for attributing idle gaps."""
    import jax
    return jax.profiler.TraceAnnotation(trace_lib.HOST_PREFIX + name)
