"""Taking the device trace of a few iterations of the steady window with
`jax.profiler`, into a directory under the system's temporary directory
that is deleted once `trace_reduce.reduce_file` has read it."""

import glob
import os
import shutil
import tempfile

import jax

from . import probes, trace_reduce

TRACE_ITERATIONS = 2  # whole iterations of the window's loop in a traced run


class WindowTracer:
    """Traces ``iterations`` whole iterations of the window's loop, from
    the boundary before iteration ``start_at`` on. The loop calls
    `at_boundary(i)` before iteration i and goes on until `done`."""

    def __init__(self, iterations=TRACE_ITERATIONS, start_at=1):
        self.iterations = iterations
        self.start_at = start_at
        self.reduction = None
        self._dir = None
        self._span = None
        self._begun = None

    @property
    def done(self):
        return self.reduction is not None

    def start(self):
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # a Python tracer slows the host
        options.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._span = probes.annotate(trace_reduce.WINDOW_PHASE)
        self._span.__enter__()

    def stop(self):
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._span = None
        try:
            paths = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if len(paths) != 1:
                raise RuntimeError(f"expected one xplane file, found {paths}")
            self.reduction = trace_reduce.reduce_file(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def at_boundary(self, iteration):
        if self.done:
            return
        if self._span is None:
            if iteration >= self.start_at:
                self._begun = iteration
                self.start()
        elif iteration - self._begun >= self.iterations:
            self.stop()
