"""Profiling hooks — a capability the reference lacks entirely
(SURVEY.md §5: "Tracing / profiling: none").

Two layers:
- **In-image (device)**: ``trace()`` wraps the JAX profiler so a
  notebook user captures an XLA trace of a training interval and views
  it in xprof/tensorboard; ``annotate()`` names host-side regions in
  that trace.
- **Control plane (host)**: the web apps already expose Prometheus
  metrics; ``profile_wsgi`` adds on-demand cProfile capture around a
  WSGI app for the pprof-style "why is this request slow" question.
"""

from __future__ import annotations

import contextlib
import cProfile
import io
import pstats
import time


class PhaseRecorder:
    """Named wall-clock phases of a repeated operation, aggregated into
    per-phase percentiles — the conformance harness's breakdown of
    where provision latency goes (POST→CR, CR→StatefulSet,
    StatefulSet→Pods, Pods→Ready).

    ``record(phase, seconds)`` takes externally-measured durations
    (e.g. computed from apiserver write-log timestamps); ``phase(name)``
    times a block inline. ``summary()`` returns per-phase
    count/p50/p95/max in milliseconds."""

    def __init__(self):
        self._samples: dict[str, list[float]] = {}

    def record(self, phase: str, seconds: float) -> None:
        self._samples.setdefault(phase, []).append(float(seconds))

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(name, time.perf_counter() - t0)

    def merge(self, other: "PhaseRecorder") -> None:
        for name, vals in other._samples.items():
            self._samples.setdefault(name, []).extend(vals)

    @staticmethod
    def _pct(vals: list[float], q: float) -> float:
        # linear interpolation between closest ranks (numpy's default
        # percentile method) — nearest-rank rounding made p95 of a
        # 20-sample storm report the 18th sample, off by half a rank
        s = sorted(vals)
        if len(s) == 1:
            return s[0]
        pos = min(max(q, 0.0), 1.0) * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        return s[lo] + (s[hi] - s[lo]) * (pos - lo)

    def summary(self) -> dict[str, dict]:
        out = {}
        for name, vals in self._samples.items():
            out[name] = {
                "count": len(vals),
                "p50_ms": round(self._pct(vals, 0.5) * 1e3, 1),
                "p95_ms": round(self._pct(vals, 0.95) * 1e3, 1),
                "p99_ms": round(self._pct(vals, 0.99) * 1e3, 1),
                "max_ms": round(max(vals) * 1e3, 1),
            }
        return out


@contextlib.contextmanager
def trace(logdir: str, *, create_perfetto_link: bool = False):
    """Capture a JAX/XLA device trace for the enclosed region:

        with profiling.trace("/home/jovyan/traces"):
            state, metrics = step(state, batch)

    View with tensorboard (profile plugin) pointed at ``logdir``.
    """
    import jax
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, *, hot: bool = False):
    """Named host region inside a device trace: a
    ``jax.profiler.TraceAnnotation``, recorded only while a profiler
    session is open (``trace()`` above, or any ``start_trace``) and on
    the clock the device planes use; with no session it is an object
    and two calls. ``hot=True`` also opens the hostsync probe's hot
    region of the same name where that probe is on, so a boundary
    takes one ``with``.

    The program's own spans, all through here (PERF.md section 3 sets
    each beside the metric that reads it):

    - ``engine.step``, and the four that partition it:
      ``engine.admit`` (with an ``engine.prefill`` around each prefill
      dispatch), ``engine.pick``, ``engine.dispatch``,
      ``engine.scatter`` — ``ContinuousBatchingEngine.step()``
    - inside ``engine.pick``: ``engine.pick.fetch`` (the pick programs'
      dispatch and the one ``jax.device_get``), then
      ``engine.pick.walk`` (the loop over the live slots); inside
      ``engine.dispatch``: ``engine.dispatch.prepare`` (the host
      arrays, the sentinel's note), then ``engine.dispatch.launch``
      (the ``paged_decode_step`` call alone)
    - ``gateway.drain`` around a working iteration of the gateway's
      drain thread, ``gateway.publish`` around its gauge block
    - ``train.shard_batch``, ``train.step``, ``train.log`` in ``fit()``
    """
    import jax
    span = jax.profiler.TraceAnnotation(name)
    if hot:
        from kubeflow_rm_tpu.analysis.jaxcheck import hostsync
        if hostsync.enabled():
            return _both(hostsync.region(name), span)
    return span


@contextlib.contextmanager
def _both(outer, inner):
    with outer, inner:
        yield


@contextlib.contextmanager
def profile_wsgi(sort: str = "cumulative", limit: int = 30):
    """cProfile a block of WSGI handling; yields a StringIO that holds
    the stats table after exit."""
    out = io.StringIO()
    prof = cProfile.Profile()
    prof.enable()
    try:
        yield out
    finally:
        prof.disable()
        pstats.Stats(prof, stream=out).sort_stats(sort).print_stats(limit)
