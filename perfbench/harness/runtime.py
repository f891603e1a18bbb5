"""What every driver shares: the count of compilations, the profiler
session, program spans cut to a window, percentiles and the tail's band."""
from __future__ import annotations

import os
import shutil
import time
from statistics import median  # noqa: F401  (the readers' median)

TRACE_DIRNAME = ".perfbench_trace"


class CompileMeter:
    """Seconds JAX spent compiling or loading compiled programs, and how
    many programs it compiled or loaded, from ``jax.monitoring``."""

    DURATIONS = ("/jax/core/compile/backend_compile_duration",
                 "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration_secs, **_):
        if event in self.DURATIONS:
            self.seconds += duration_secs
        if event == self.DURATIONS[0]:
            self.events += 1

    def snapshot(self):
        return (self.seconds, self.events)


class ProfilerSession:
    """One ``jax.profiler`` trace into a fixed directory of the checkout,
    read once and then deleted: traces are large and the host keeps every
    block ever written."""

    def __init__(self, root: str):
        self.dir = os.path.join(root, TRACE_DIRNAME)
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        kwargs = {}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1
            opts.host_tracer_level = 2
            kwargs["profiler_options"] = opts
        except AttributeError:
            pass
        jax.profiler.start_trace(self.dir, **kwargs)
        self.t_start = time.monotonic()

    def stop(self):
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_start

    def read(self):
        from perfbench.harness import trace

        try:
            return trace.Trace.from_file(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _rank(n: int, q: float) -> int:
    """Index of the nearest-rank percentile ``q`` in a sorted list of ``n``."""
    return max(0, min(n - 1, int(-(-q * n // 100)) - 1))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    xs = sorted(values)
    return xs[_rank(len(xs), q)]


def band_mean(values, lo: float = 90, hi: float = 99) -> float:
    """Mean of the values from the one at percentile ``lo`` to the one at
    percentile ``hi``, both by nearest rank and both in it, of a non-empty
    list: the slow tenth with the slowest hundredth set aside. Where the
    values fall into a few classes it moves with the classes' shares and
    does not jump when a share crosses a percentile; a list too short to
    tell the two ranks apart gives the one value at both."""
    xs = sorted(values)
    band = xs[_rank(len(xs), lo):_rank(len(xs), hi) + 1]
    return sum(band) / len(band)


def make_autodist(strategy_builder, chips: int):
    """One ``AutoDist`` over the cell's chips. On the machine a cell is
    measured on, the chips are all the devices there are and the program
    finds them itself. Where there are more (the tests' eight virtual CPU
    devices), the mesh is built over the first ``chips`` of them."""
    import jax
    from autodist_tpu.api import AutoDist
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.resource_spec import ResourceSpec

    AutoDist.reset_default()
    if len(jax.devices()) == chips:
        return AutoDist(strategy_builder=strategy_builder)
    spec = ResourceSpec(resource_dict={"nodes": [
        {"address": "localhost", "chips": chips, "chief": True}]})
    autodist = AutoDist(strategy_builder=strategy_builder, resource_spec=spec)
    autodist._mesh = build_mesh(spec, axes=autodist.mesh_axes,
                                devices=jax.devices()[:chips])
    return autodist
