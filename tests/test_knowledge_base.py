"""Knowledge base: profile store, RBF/NN derivation, scope widening
(paper Sec. 3.2.1 / 3.2.3)."""
import math
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import KnowledgeBase, Origin, PlatformConfig, Profile
from repro.core.knowledge_base import RBFNetwork, nearest_neighbour
from repro.core.spec import Workload


def prof(sct, dims, share, time=1.0, fission="L2", overlap=4):
    return Profile(sct_id=sct, workload=Workload(tuple(dims)),
                   share_a=share, best_time=time,
                   config=PlatformConfig(fission_level=fission,
                                         overlap=overlap))


class TestStore:
    def test_best_time_wins(self):
        kb = KnowledgeBase()
        kb.store(prof("p", (1024,), 0.8, time=2.0))
        kb.store(prof("p", (1024,), 0.9, time=1.0))
        kb.store(prof("p", (1024,), 0.5, time=3.0))   # worse: ignored
        assert kb.exact("p", Workload((1024,))).share_a == 0.9

    def test_persistence_roundtrip(self, tmp_path):
        path = os.path.join(tmp_path, "kb.json")
        kb = KnowledgeBase(path)
        kb.store(prof("p", (512, 512), 0.75))
        kb2 = KnowledgeBase(path)
        got = kb2.exact("p", Workload((512, 512)))
        assert got is not None and got.share_a == 0.75
        assert got.config.fission_level == "L2"


class TestRBF:
    def test_interpolates_exactly_at_nodes(self):
        x = np.array([[1.0], [2.0], [3.0]])
        y = np.array([10.0, 20.0, 15.0])
        net = RBFNetwork().fit(x, y)
        np.testing.assert_allclose(net.predict(x), y, atol=1e-3)

    def test_between_nodes_sane(self):
        x = np.array([[0.0], [1.0]])
        y = np.array([0.0, 1.0])
        p = float(RBFNetwork().fit(x, y).predict(np.array([0.5])))
        assert 0.2 < p < 0.8

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.floats(0.5, 13.5), min_size=3, max_size=8,
                    unique=True))
    def test_node_recovery_property(self, exps):
        # nodes spaced >= 0.25 in log space (coincident nodes make the
        # regularised solve interpolate their mean, which is correct
        # behaviour but not what this property asserts)
        exps = sorted(exps)
        exps = [e for i, e in enumerate(exps)
                if i == 0 or e - exps[i - 1] > 0.25]
        if len(exps) < 3:
            return
        x = np.exp(np.array(exps))[:, None]
        y = np.linspace(0, 1, len(x))
        net = RBFNetwork().fit(np.log1p(x), y)
        np.testing.assert_allclose(net.predict(np.log1p(x)), y, atol=5e-2)


class TestDerivation:
    def test_same_sct_scope_first(self):
        kb = KnowledgeBase()
        kb.store(prof("A", (1000,), 0.6))
        kb.store(prof("A", (4000,), 0.8))
        kb.store(prof("B", (2000,), 0.1))
        got = kb.derive("A", Workload((2000,)))
        assert got.origin is Origin.DERIVED
        assert 0.4 < got.share_a < 0.95      # from A's profiles, not B's

    def test_scope_widens_to_same_workload(self):
        kb = KnowledgeBase()
        kb.store(prof("B", (2000,), 0.33))
        got = kb.derive("A", Workload((2000,)))
        assert got is not None
        assert got.share_a == pytest.approx(0.33, abs=0.05)

    def test_empty_kb_returns_none(self):
        assert KnowledgeBase().derive("A", Workload((128,))) is None

    def test_nn_used_for_high_dims(self):
        kb = KnowledgeBase()
        kb.store(prof("A", (2, 3, 4, 5), 0.25, fission="L3"))
        kb.store(prof("A", (100, 100, 100, 100), 0.9, fission="L1"))
        got = kb.derive("A", Workload((3, 3, 4, 5)))
        assert got.share_a == 0.25            # nearest neighbour
        assert got.config.fission_level == "L3"

    def test_far_workload_gets_the_profiles_mean_share(self):
        # a new SCT whose workload lies far from every profile of the same
        # dimensionality: the Gaussians vanish there, and the derived share
        # is the profiles' mean, not a share near 0 that none of them has
        kb = KnowledgeBase()
        kb.store(prof("filter", (4096, 4096), 0.8))
        kb.store(prof("fft", (256, 65536), 0.75))
        got = kb.derive("nbody", Workload((8192, 4)))
        assert 0.75 <= got.share_a <= 0.8

    def test_monotone_interpolation_tracks_size(self):
        """Table 5-style: derived share follows workload size trend."""
        kb = KnowledgeBase()
        for n, s in [(512, 0.5), (2048, 0.7), (8192, 0.9)]:
            kb.store(prof("img", (n, n), s))
        small = kb.derive("img", Workload((700, 700))).share_a
        large = kb.derive("img", Workload((6000, 6000))).share_a
        assert small < large


def test_nearest_neighbour_log_scale():
    pts = np.array([[1000.0], [1_000_000.0]])
    assert nearest_neighbour(np.array([2000.0]), pts) == 0
    assert nearest_neighbour(np.array([400_000.0]), pts) == 1
