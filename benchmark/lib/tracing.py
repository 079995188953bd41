"""Start and stop the profiler around a part of the window, and reduce what
it wrote. The trace goes under ``<checkout>/.bench_trace`` (git-ignored) and
is removed once it is read: traces are large and the host keeps every block
ever written."""
import os
import shutil
import time

from lib import trace_reduce


class WindowTrace:
    def __init__(self, root):
        self.dir = os.path.join(root, ".bench_trace")
        self.t0 = None  # when the trace began; None until then

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceAnnotations, not every frame
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def reduce(self, program_patterns, align=None):
        try:
            trace = trace_reduce.load(trace_reduce.find_xplane(self.dir))
            return trace_reduce.reduce(trace,
                                       program_patterns=program_patterns,
                                       align=align)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

