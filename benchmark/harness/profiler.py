"""The profiler around the last seconds of a traced run's window."""

import os
import shutil

from benchmark.harness import trace


class TailTrace:
    """Starts the profiler once ``start_at`` has passed and stops it when
    asked: the traced stretch lies at the window's end, so that stopping
    (which writes the file, and takes seconds) happens outside it."""

    def __init__(self, enabled, logdir, start_at):
        self.enabled, self.logdir, self.start_at = enabled, logdir, start_at
        self.running = self.started = False

    def maybe_start(self, now):
        if not self.enabled or self.started or now < self.start_at:
            return
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the host's spans are TraceMe's
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.running = self.started = True

    def stop(self):
        """The loaded trace, or None when nothing was traced."""
        if not self.running:
            return None
        import jax

        jax.profiler.stop_trace()
        self.running = False
        return trace.load(trace.newest_xplane(self.logdir))
