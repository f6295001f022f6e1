"""TrainingProfiler unit coverage (ISSUE-3 satellite).

The profiler's ``input_bound_fraction`` is the one-number "am I
input-bound?" answer operators act on; its edge cases (no stages yet,
zero totals, one stage missing) must read as "unknown" (None), never
divide by zero or claim 0%/100% from vacuous data. ``summary()`` is
consumed by ``fit(profile=True)`` logging and bench extras, so its
dict shape is a contract.
"""

import time

import pytest

from analytics_zoo_tpu.common.log import TimerStat
from analytics_zoo_tpu.learn.profiler import TrainingProfiler


def _record(profiler: TrainingProfiler, stage: str, dt: float) -> None:
    """Record an exact duration on a stage (timing() would add its own
    measured epsilon, which the zero-total edge cases must not see)."""
    stat = profiler.timer._stats.setdefault(stage, TimerStat(stage))
    stat.record(dt)


class TestInputBoundFraction:
    def test_no_stages_recorded_is_unknown(self):
        assert TrainingProfiler().input_bound_fraction is None

    def test_missing_train_step_is_unknown(self):
        p = TrainingProfiler()
        _record(p, "data_wait", 0.5)
        assert p.input_bound_fraction is None

    def test_missing_data_wait_is_unknown(self):
        p = TrainingProfiler()
        _record(p, "train_step", 0.5)
        assert p.input_bound_fraction is None

    def test_zero_totals_is_unknown_not_zero_division(self):
        """Both stages present but with zero accumulated time (e.g.
        clock granularity on trivial models): None, not 0/0."""
        p = TrainingProfiler()
        _record(p, "data_wait", 0.0)
        _record(p, "train_step", 0.0)
        assert p.input_bound_fraction is None

    def test_fraction_of_loop_time(self):
        p = TrainingProfiler()
        _record(p, "data_wait", 3.0)
        _record(p, "train_step", 1.0)
        assert p.input_bound_fraction == pytest.approx(0.75)

    def test_other_stages_do_not_dilute(self):
        """Only data_wait vs train_step define the fraction; epoch
        wall time (a superset of both) must not enter the ratio."""
        p = TrainingProfiler()
        _record(p, "data_wait", 1.0)
        _record(p, "train_step", 1.0)
        _record(p, "epoch", 100.0)
        assert p.input_bound_fraction == pytest.approx(0.5)

    def test_zero_data_wait_with_real_steps_is_zero(self):
        """A perfectly compute-bound loop reads 0.0 (known), not
        None (unknown): the totals sum is positive."""
        p = TrainingProfiler()
        _record(p, "data_wait", 0.0)
        _record(p, "train_step", 2.0)
        assert p.input_bound_fraction == pytest.approx(0.0)


class TestSummary:
    def test_empty_summary(self):
        assert TrainingProfiler().summary() == {}

    def test_summary_shape(self):
        """Per-stage dicts carry exactly the count/total/avg/max/min
        keys fit(profile=True) logs and bench extras embed."""
        p = TrainingProfiler()
        _record(p, "data_wait", 0.25)
        _record(p, "data_wait", 0.75)
        s = p.summary()
        assert set(s) == {"data_wait"}
        entry = s["data_wait"]
        assert set(entry) == {"count", "total_s", "avg_s", "max_s",
                              "min_s"}
        assert entry["count"] == 2
        assert entry["total_s"] == pytest.approx(1.0)
        assert entry["max_s"] == pytest.approx(0.75)
        assert entry["min_s"] == pytest.approx(0.25)
        assert entry["avg_s"] == pytest.approx(0.5)

    def test_timing_context_measures_wall_time(self):
        p = TrainingProfiler()
        with p.timing("train_step"):
            time.sleep(0.01)
        entry = p.summary()["train_step"]
        assert entry["count"] == 1
        assert entry["total_s"] >= 0.005

    def test_stage_durations_mirror_into_registry(self):
        """Every profiler stage also lands in the process-wide
        zoo_learn_stage_duration_seconds family (the shared scrape
        vocabulary of serving + training)."""
        from analytics_zoo_tpu.obs.metrics import get_registry

        fam = get_registry().get("zoo_learn_stage_duration_seconds")
        child = fam.labels(stage="profiler_test_stage")
        before = child.snapshot()["count"]
        p = TrainingProfiler()
        with p.timing("profiler_test_stage"):
            pass
        assert child.snapshot()["count"] == before + 1


class TestDeviceTrace:
    """``fit(trace_dir=...)`` (ISSUE 24): a device-only trace. With the
    host tracer on at any level the runtime records one event per row
    of every batch it re-tiles (16.5 million for 16 steps of an image
    model, PERF.md section 6), and the names a training trace is read
    by are on the device planes."""

    @staticmethod
    def _estimator():
        import flax.linen as nn
        import jax.numpy as jnp
        import optax

        from analytics_zoo_tpu.learn.estimator import Estimator

        return Estimator(nn.Dense(2),
                         loss=lambda p, t: jnp.mean((p - t) ** 2),
                         optimizer=optax.sgd(0.1))

    def test_fit_trace_dir_takes_a_device_only_trace(self, tmp_path,
                                                     monkeypatch):
        import glob

        import jax
        import numpy as np

        started = []
        real_start = jax.profiler.start_trace

        def start_trace(log_dir, *args, **kwargs):
            started.append((log_dir, kwargs.get("profiler_options")))
            return real_start(log_dir, *args, **kwargs)

        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        x = np.random.default_rng(0).random((16, 4), np.float32)
        y = x[:, :2]
        est = self._estimator()
        trace_dir = str(tmp_path / "trace")
        est.fit((x, y), batch_size=8, epochs=1, trace_dir=trace_dir)

        (log_dir, options), = started
        assert log_dir == trace_dir
        assert options.host_tracer_level == 0
        assert options.python_tracer_level == 0
        assert glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
        assert not est.last_profile._tracing
        # the stage timers run beside the trace, as with profile=True
        assert est.last_profile.summary()["train_step"]["count"] == 2
        # the trace belongs to that one call: the next fit starts none
        est.fit((x, y), batch_size=8, epochs=2)
        assert len(started) == 1

    def test_no_host_annotation_left_in_learn(self):
        """A ``TraceAnnotation`` is recorded by the host tracer only;
        with the trace device-only it would be dead code."""
        import os
        import re

        import analytics_zoo_tpu.learn as learn

        root = os.path.dirname(learn.__file__)
        for name in sorted(os.listdir(root)):
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert not re.search(
                        r"TraceMe|TraceAnnotation", f.read()), name
