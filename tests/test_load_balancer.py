"""Dynamic load balancing: lbt threshold + corrector (paper Sec. 3.3)."""
import pytest

from repro.core import Distribution, ExecutionStats, LoadBalancer


def stats(times, share=0.8):
    return ExecutionStats(times=list(times), share_a=share)


class TestDetector:
    def test_balanced_run_keeps_lbt_low(self):
        lb = LoadBalancer(max_dev=0.85)
        for _ in range(10):
            assert not lb.observe(stats([1.0, 0.95, 0.9]))
        assert lb.lbt < 0.1

    def test_unbalanced_takes_3_to_4_runs(self):
        """Paper: weight=2/3 -> 3-4 consecutive unbalanced runs trigger."""
        lb = LoadBalancer(max_dev=0.85, weight=2 / 3, trigger=0.9)
        fired_at = None
        for n in range(1, 10):
            if lb.observe(stats([1.0, 0.4])):
                fired_at = n
                break
        assert fired_at in (3, 4)

    def test_sporadic_unbalance_filtered(self):
        lb = LoadBalancer(max_dev=0.85)
        seq = [[1.0, 0.95], [1.0, 0.4], [1.0, 0.95], [1.0, 0.97],
               [1.0, 0.4], [1.0, 0.96]]
        assert not any(lb.observe(stats(t)) for t in seq)

    def test_c_factor_tolerates_by_design_unbalance(self):
        lb_strict = LoadBalancer(max_dev=0.85, c_factor=1.0)
        lb_loose = LoadBalancer(max_dev=0.85, c_factor=0.8)
        dev = 0.7
        assert lb_strict.is_unbalanced(dev)
        assert not lb_loose.is_unbalanced(dev)

    def test_deviation_definition(self):
        assert stats([2.0, 1.0]).deviation == pytest.approx(0.5)
        assert stats([1.0, 1.0]).deviation == pytest.approx(1.0)


class TestCorrector:
    def test_adjust_moves_towards_faster_class(self):
        lb = LoadBalancer()
        cur = Distribution(a=0.5, b=0.5)
        new = lb.adjust(cur, stats_a=1.0, stats_b=3.0)
        assert new.a > 0.5
        assert lb.balance_ops == 1

    def test_consecutive_adjusts_accelerate(self):
        """Shifting phase of Fig. 11: repeated one-direction corrections
        grow the step (adaptive search doubling)."""
        lb = LoadBalancer()
        cur = Distribution(a=0.3, b=0.7)
        deltas = []
        for _ in range(5):
            new = lb.adjust(cur, 1.0, 4.0)
            deltas.append(new.a - cur.a)
            cur = new
        assert deltas[-1] > deltas[0]


class TestSchedulerDetector:
    @staticmethod
    def _scheduler():
        from repro.core import (AcceleratorPlatform, DeviceInfo, HostPlatform,
                                KnowledgeBase, Scheduler)
        from repro.core.simulator import SimDevice, SimulatedExecutor
        sim = SimulatedExecutor([SimDevice("gpu0", "gpu", flops=1e12),
                                 SimDevice("cpu0", "cpu", flops=1e11)])
        # max_dev > 1: every run counts as unbalanced
        return Scheduler(host=HostPlatform(DeviceInfo("cpu0", "cpu")),
                         accel=AcceleratorPlatform([DeviceInfo("gpu0", "gpu")]),
                         executor=sim, kb=KnowledgeBase(),
                         balancer=LoadBalancer(max_dev=1.5))

    def test_switching_sct_restarts_the_detector(self):
        """Another SCT's unbalanced runs must not trigger an adjustment of
        the SCT that follows it."""
        import numpy as np
        from repro.core import kernel, vector
        add = kernel(lambda x: x + 1, name="add_one",
                     inputs=[vector("x")], outputs=[vector("z")])
        neg = kernel(lambda x: -x, name="negate",
                     inputs=[vector("x")], outputs=[vector("z")])
        x = {"x": np.arange(256, dtype=np.float32)}
        sched = self._scheduler()
        for _ in range(3):
            sched.run(add, dict(x))
        assert sched.balancer.lbt >= sched.balancer.trigger
        assert sched.run(neg, dict(x)).action == "derived"
        assert sched.run(neg, dict(x)).action == "reused"
        assert sched.run(neg, dict(x)).action == "reused"
        assert sched.run(neg, dict(x)).action == "adjusted"
