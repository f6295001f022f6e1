"""Training-side profiling.

Round-1 gap (VERDICT row 29): the reference profiles serving via
``Timer`` and training via BigDL ``Metrics`` counters + Ray runners'
``profile=True`` per-epoch time stats
(ref: zoo/.../serving/engine/Timer.scala:24-90,
pyzoo/zoo/orca/learn/pytorch/pytorch_ray_estimator.py:150-190,
torch_runner.py:308-316). Here training profiling has three layers
that share one pair of clock readings per stage (``estimator._stage``):

- The span ring (``obs/tracing.Tracer``), always on: every ``fit``
  call's ``data_wait`` / ``train_step`` and the seven spans around them,
  each with its start, its end and the step's index, on the clock a
  device trace is on (docs/observability.md "Training spans"). It is
  what says *which* waits left the chip idle.
- ``TrainingProfiler`` (``fit(profile=True)``): the same two stages'
  durations summed, with the same count/avg/max/min summary shape as
  the serving Timer. ``input_bound_fraction`` is the share of the
  caller's loop spent in ``next(batches)``: it counts waits the device
  never felt (steps already queued) with the ones it did, so it is an
  upper bound on "am I input-bound?", not the answer. Since ISSUE-2
  every stage duration also lands in the process-wide obs registry
  (``zoo_learn_stage_duration_seconds{stage=...}``), so training and
  serving share one scrape vocabulary.
- XLA device tracing: a device-only ``jax.profiler`` trace written to
  a TensorBoard-loadable directory when ``trace_dir`` is set -- answers
  "what is the chip doing?", op by op, each named by its module path
  and scope (the reference has no analog; BigDL had no device
  profiler). ``fit`` writes the call's spans beside it as Chrome trace
  JSON on the same clock.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from analytics_zoo_tpu.common.log import Timer
from analytics_zoo_tpu.obs.metrics import get_registry

_M_LEARN_STAGE = get_registry().histogram(
    "zoo_learn_stage_duration_seconds",
    "Training stage latency (data_wait, train_step, epoch, ...)",
    labelnames=("stage",))


class TrainingProfiler:
    """Stage timers + optional jax.profiler trace for one fit() run."""

    def __init__(self, trace_dir: Optional[str] = None):
        self.timer = Timer(mirror=_M_LEARN_STAGE)
        self.trace_dir = trace_dir
        self._tracing = False

    # ------------------------------------------------------ stage timing --
    def timing(self, stage: str):
        """Host timer for the stage (a context manager)."""
        return self.timer.timing(stage)

    def record(self, stage: str, elapsed: float) -> None:
        """A stage duration the caller timed itself (``fit`` reads the
        clock once for the span ring and for this)."""
        self.timer.record(stage, elapsed)

    # ------------------------------------------------------- device trace --
    def start_trace(self) -> None:
        """Device planes only. With the host tracer on (at any level)
        the runtime records one event per row of every batch it
        re-tiles for the device: 16 steps of an image model wrote 16.5
        million host events, a 500 MB trace that stalled the steps it
        was meant to show (PERF.md section 6, PR 22). The device planes
        carry every op with its module path and scope, which is what a
        training trace is read by (docs/observability.md)."""
        if self.trace_dir and not self._tracing:
            import jax

            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self._tracing = True

    def stop_trace(self) -> None:
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    # ----------------------------------------------------------- results --
    def summary(self) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for name, stat in self.timer.stats().items():
            out[name] = {"count": stat.count,
                         "total_s": round(stat.total, 6),
                         "avg_s": round(stat.avg, 6),
                         "max_s": round(stat.max, 6),
                         "min_s": round(stat.min if stat.count else 0.0,
                                        6)}
        return out

    @property
    def input_bound_fraction(self) -> Optional[float]:
        """Fraction of loop time spent waiting on data -- > ~0.3 means
        the input pipeline, not the chip, sets throughput."""
        stats = self.timer.stats()
        data = stats.get("data_wait")
        step = stats.get("train_step")
        if not data or not step or (data.total + step.total) == 0:
            return None
        return data.total / (data.total + step.total)
