"""Locality-aware domain decomposition (paper Sec. 3.1) — unit + property.

The constraint system under test, for every vector V and kernels K1, K2
sharing it:  epu(V) % nu(V,K) == 0,  #V^j % (epu/nu) == 0,
#V^j % wgs_j(K) == 0, and the partitions tile the domain exactly.
"""
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DecompositionError, ExecutionSlot, KernelSpec,
                        Pipeline, build_plan, kernel, scalar, validate,
                        vector)
from repro.core.spec import Transfer


def two_kernel_pipeline(epu=4, nu=2, copy_weights=False):
    k1 = kernel(lambda x: x * 2, name="k1",
                inputs=[vector("x", epu=epu)],
                outputs=[vector("mid", epu=epu)],
                work_per_thread=nu)
    k2_in = [vector("mid", epu=epu)]
    if copy_weights:
        k2_in.append(vector("w", copy=True))
    k2 = kernel(lambda m, *a: m + 1, name="k2", inputs=k2_in,
                outputs=[vector("y", epu=epu)], work_per_thread=nu)
    return Pipeline(k1, k2)


class TestBuildPlan:
    def test_shared_edge_units(self):
        sct = two_kernel_pipeline(epu=4)
        plan = build_plan(sct, {"x": (64,), "mid": (64,), "y": (64,)})
        assert plan.domain_units == 16
        assert not plan.vectors["x"].copy

    def test_copy_vectors_replicated(self):
        sct = two_kernel_pipeline(epu=4, copy_weights=True)
        plan = build_plan(sct, {"x": (64,), "mid": (64,), "y": (64,),
                                "w": (10,)})
        assert plan.vectors["w"].copy

    def test_locality_violation_rejected(self):
        """Vectors disagreeing on unit count cannot share a tree."""
        k1 = kernel(lambda x: x, name="k1", inputs=[vector("x", epu=4)],
                    outputs=[vector("mid", epu=4)])
        k2 = kernel(lambda m: m, name="k2", inputs=[vector("mid", epu=8)],
                    outputs=[vector("y", epu=8)])
        with pytest.raises(DecompositionError):
            build_plan(Pipeline(k1, k2), {"x": (64,), "mid": (64,),
                                          "y": (64,)})

    def test_extent_not_multiple_of_epu(self):
        sct = two_kernel_pipeline(epu=5)
        with pytest.raises(DecompositionError):
            build_plan(sct, {"x": (64,), "mid": (64,), "y": (64,)})

    def test_epu_not_multiple_of_nu(self):
        sct = two_kernel_pipeline(epu=3, nu=2)
        plan = build_plan(sct, {"x": (63,), "mid": (63,), "y": (63,)})
        slots = [ExecutionSlot("d0", "gpu")]
        with pytest.raises(DecompositionError):
            plan.partition(slots, [1.0])


class TestPartition:
    def test_even_split_validates(self):
        sct = two_kernel_pipeline(epu=4)
        plan = build_plan(sct, {"x": (64,), "mid": (64,), "y": (64,)})
        slots = [ExecutionSlot("g0", "gpu", wgs={"k1": 8, "k2": 8}),
                 ExecutionSlot("c0", "cpu", wgs={"k1": 8, "k2": 8})]
        part = plan.partition(slots, [0.5, 0.5])
        validate(plan, part)
        assert sum(part.sizes("x")) == 64

    def test_uneven_shares_quantised(self):
        sct = two_kernel_pipeline(epu=4)
        plan = build_plan(sct, {"x": (64,), "mid": (64,), "y": (64,)})
        slots = [ExecutionSlot("g0", "gpu", wgs={"k1": 8, "k2": 8}),
                 ExecutionSlot("c0", "cpu", wgs={"k1": 4, "k2": 4})]
        part = plan.partition(slots, [0.7, 0.3])
        validate(plan, part)
        assert sum(part.units) == plan.domain_units

    def test_slices_tile_domain(self):
        sct = two_kernel_pipeline(epu=2)
        plan = build_plan(sct, {"x": (32,), "mid": (32,), "y": (32,)})
        slots = [ExecutionSlot(f"d{i}", "gpu") for i in range(3)]
        part = plan.partition(slots, [0.5, 0.3, 0.2])
        xs = jnp.arange(32.0)
        pieces = part.slices("x", xs)
        assert jnp.concatenate(pieces).tolist() == xs.tolist()


@settings(max_examples=60, deadline=None)
@given(
    units=st.integers(4, 200),
    epu=st.sampled_from([1, 2, 4, 8]),
    n_slots=st.integers(1, 6),
    seed=st.integers(0, 2 ** 31),
)
def test_partition_properties(units, epu, n_slots, seed):
    """Property: any share vector yields a tiling, quantised partitioning
    covering the domain exactly (paper constraint 1: V = U_j V^j)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    raw = rng.random(n_slots) + 1e-3
    shares = (raw / raw.sum()).tolist()
    shares[-1] = 1.0 - sum(shares[:-1])

    extent = units * epu
    sct = two_kernel_pipeline(epu=epu, nu=1)
    plan = build_plan(sct, {"x": (extent,), "mid": (extent,),
                            "y": (extent,)})
    slots = [ExecutionSlot(f"d{i}", "gpu" if i % 2 else "cpu")
             for i in range(n_slots)]
    part = plan.partition(slots, shares)
    assert sum(part.units) == plan.domain_units
    assert sum(part.sizes("x")) == extent
    offs = part.offsets("x")
    szs = part.sizes("x")
    for i in range(1, n_slots):
        assert offs[i] == offs[i - 1] + szs[i - 1]
    if not part.relaxed:
        validate(plan, part)
