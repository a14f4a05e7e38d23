"""Workload distribution: binary search + adaptive binary search
(paper Sec. 3.2.2 / 3.3.1) — unit + property + convergence."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (AdaptiveBinarySearch, Distribution,
                        WorkloadDistributionGenerator, balance_until_stable,
                        run_binary_search)


def make_measure(speed_a: float, speed_b: float):
    """Times for a split: t_a = share_a/speed_a, t_b = share_b/speed_b."""
    def measure(d: Distribution):
        ta = d.a / speed_a if speed_a > 0 else math.inf
        tb = d.b / speed_b if speed_b > 0 else math.inf
        return ta, tb
    return measure


class TestGenerator:
    def test_transferable_halves(self):
        """Paper: transferableSize(n, size) = size / 2^n."""
        g = WorkloadDistributionGenerator()
        for n in range(8):
            assert g.transferable_size() == pytest.approx(0.5 ** n)
            g.next()
            g.feedback(1.0, 2.0)

    def test_binds_to_winner(self):
        """Paper: the winner's half of the transferable partition binds;
        the other half becomes the next transferable partition."""
        g = WorkloadDistributionGenerator()
        g.next()
        g.feedback(1.0, 2.0)        # a faster
        assert g.bound_a == pytest.approx(0.5)
        assert g.bound_b == 0.0
        assert g.transferable == pytest.approx(0.5)

    def test_feedback_requires_next(self):
        g = WorkloadDistributionGenerator()
        with pytest.raises(RuntimeError):
            g.feedback(1.0, 2.0)

    @settings(max_examples=40, deadline=None)
    @given(sa=st.floats(0.1, 10), sb=st.floats(0.1, 10))
    def test_converges_to_speed_ratio(self, sa, sb):
        """The optimum evens completion times: share_a* = sa/(sa+sb)."""
        dist, iters = run_binary_search(make_measure(sa, sb),
                                        precision=1e-4, max_iters=40)
        assert dist.a == pytest.approx(sa / (sa + sb), abs=2e-3)


class TestAdaptiveBinarySearch:
    def test_doubling_after_shifts(self):
        """>2 shifts in one direction double the transferable size."""
        s = AdaptiveBinarySearch(Distribution(a=0.2, b=0.8), step=0.02)
        sizes = []
        for _ in range(6):
            s.next()
            s.feedback(1.0, 5.0)        # a keeps winning -> shift right
            sizes.append(s.transferable)
        assert sizes[3] > sizes[1]      # doubling kicked in
        assert s.center.a > 0.2         # moved towards a

    def test_halving_on_alternation(self):
        s = AdaptiveBinarySearch(Distribution(a=0.5, b=0.5), step=0.08)
        s.next(); s.feedback(1.0, 2.0)
        t0 = s.transferable
        s.next(); s.feedback(2.0, 1.0)  # winner flips -> halve
        assert s.transferable == pytest.approx(t0 / 2)

    @settings(max_examples=30, deadline=None)
    @given(sa=st.floats(0.2, 5), sb=st.floats(0.2, 5),
           start=st.floats(0.05, 0.95))
    def test_rebalances_from_any_start(self, sa, sb, start):
        d, ops = balance_until_stable(
            make_measure(sa, sb), Distribution(a=start, b=1 - start),
            precision=1e-3, max_iters=200)
        assert d.a == pytest.approx(sa / (sa + sb), abs=0.05)

    def test_load_fluctuation_recovery(self):
        """Fig. 11: CPU slows down mid-run; the search follows."""
        speed_b = [1.0]
        def measure(d):
            return d.a / 4.0, d.b / speed_b[0]
        d, _ = balance_until_stable(measure, Distribution(a=0.8, b=0.2),
                                    precision=1e-3)
        assert d.a == pytest.approx(0.8, abs=0.05)
        speed_b[0] = 0.25               # external load: 4x slower CPU
        d2, _ = balance_until_stable(measure, d, precision=1e-3)
        assert d2.a == pytest.approx(4 / 4.25, abs=0.05)


class TestDistribution:
    def test_per_device_static_split(self):
        d = Distribution(a=0.8, b=0.2)
        shares = d.per_device([3.0, 1.0], [1.0])
        assert shares[0] == pytest.approx(0.6)
        assert shares[1] == pytest.approx(0.2)
        assert shares[2] == pytest.approx(0.2)
        assert sum(shares) == pytest.approx(1.0)

    def test_validation(self):
        with pytest.raises(ValueError):
            Distribution(a=0.7, b=0.7)
