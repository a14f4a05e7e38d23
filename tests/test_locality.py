"""Locality pipeline: plan cache, persistent pools, zero-copy merge,
partitioned residency (ISSUE 7).

Covers the plan-cache correctness matrix (hit on recurrent runs,
invalidation on quarantine/reinstatement and on adjusted shares,
bit-identical outputs vs. the uncached path including a fault-injected
repartition run), the in-place merge and its legacy-equivalence, pool
persistence, residency handoff/fallback, and the satellite fixes
(`_per_slot_shares` zero-total fallback, user merge-fn precedence).
"""
import math

import numpy as np
import pytest

from repro.core import (AcceleratorPlatform, DeviceInfo, ExecutionSlot,
                        FaultInjector, FaultPolicy, HostPlatform,
                        KnowledgeBase, LoadBalancer, PlanCache,
                        PlatformConfig, Profile, Scheduler, Session,
                        ThreadedExecutor, Workload, build_plan, kernel,
                        scalar, vector)

POLICY = FaultPolicy(watchdog_multiple=1e6)   # no spurious watchdog on CI


def saxpy_tree():
    return kernel(lambda a, x, y: a * x + y, name="saxpy",
                  inputs=[scalar("a"), vector("x"), vector("y")],
                  outputs=[vector("z")])


def chain_trees():
    k2 = kernel(lambda a, z: z * a, name="scale",
                inputs=[scalar("a"), vector("z")], outputs=[vector("w")])
    k3 = kernel(lambda w, y: w + y, name="addy",
                inputs=[vector("w"), vector("y")], outputs=[vector("v")])
    return [saxpy_tree(), k2, k3]


def saxpy_arrays(n=256, a=2.0):
    return {"a": np.float32(a),
            "x": np.arange(n, dtype=np.float32),
            "y": np.ones(n, dtype=np.float32)}


def make_scheduler(executor, **kw):
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=4),
                        topology={"L2": 2, "NO_FISSION": 1})
    accel = AcceleratorPlatform([DeviceInfo("gpu0", "gpu")], max_overlap=2)
    kw.setdefault("balancer", LoadBalancer(max_dev=0.0))
    kw.setdefault("kb", KnowledgeBase())
    return Scheduler(host=host, accel=accel, executor=executor, **kw)


def three_slot_part(sct, n=256, shares=(0.5, 0.25, 0.25)):
    plan = build_plan(sct, {"x": (n,), "y": (n,)})
    slots = [ExecutionSlot("gpu0/q0", "gpu"),
             ExecutionSlot("cpu0/f0", "cpu"),
             ExecutionSlot("cpu0/f1", "cpu")]
    return plan.partition(slots, list(shares))


def make_profile(sct, n=256, share=0.5):
    return Profile(sct_id=sct.unique_id(), workload=Workload((n,)),
                   share_a=share, config=PlatformConfig(),
                   best_time=math.inf)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

class TestPlanCache:
    def test_recurrent_run_hits_cache(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        sct, arrays = saxpy_tree(), saxpy_arrays()
        r1 = sched.run(sct, dict(arrays))
        r2 = sched.run(sct, dict(arrays))
        r3 = sched.run(sct, dict(arrays))
        assert not r1.stats.plan_cache_hit
        assert r2.stats.plan_cache_hit and r3.stats.plan_cache_hit
        assert sched.plan_cache.hits == 2
        assert sched.plan_cache.misses == 1

    def test_workload_change_misses(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        sct = saxpy_tree()
        sched.run(sct, saxpy_arrays(n=256))
        r = sched.run(sct, saxpy_arrays(n=128))
        assert not r.stats.plan_cache_hit

    def test_bit_identical_to_uncached_path(self):
        sct, arrays = saxpy_tree(), saxpy_arrays()
        legacy = make_scheduler(ThreadedExecutor(
            policy=POLICY, persistent_pool=False, inplace_merge=False),
            plan_cache=False)
        expected = np.copy(legacy.run(sct, dict(arrays)).outputs["z"])
        cached = make_scheduler(ThreadedExecutor(policy=POLICY))
        for _ in range(3):
            got = np.copy(np.asarray(cached.run(sct,
                                                dict(arrays)).outputs["z"]))
            np.testing.assert_array_equal(expected, got)

    def test_bit_identical_under_fault_repartition(self):
        sct, arrays = saxpy_tree(), saxpy_arrays()
        legacy = make_scheduler(ThreadedExecutor(
            policy=POLICY, persistent_pool=False, inplace_merge=False),
            plan_cache=False)
        expected = np.copy(legacy.run(sct, dict(arrays)).outputs["z"])
        inj = FaultInjector(crash_on_call={"gpu0": [2]})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY, injector=inj))
        sched.run(sct, dict(arrays))                 # populate the cache
        r = sched.run(sct, dict(arrays))             # cache hit + crash
        assert r.stats.plan_cache_hit
        assert r.stats.retries == 1
        np.testing.assert_array_equal(
            expected, np.copy(np.asarray(r.outputs["z"])))

    def test_invalidated_on_quarantine_and_reinstatement(self):
        inj = FaultInjector(crash_on_call={"gpu0": [2, 3]})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY, injector=inj))
        sched.health.quarantine_after = 1
        sched.health.probe_after = 1
        sct, arrays = saxpy_tree(), saxpy_arrays()
        sched.run(sct, dict(arrays))                 # clean, cache filled
        sched.run(sct, dict(arrays))                 # gpu0 crash -> quarantine
        before = sched.plan_cache.invalidations
        r = sched.run(sct, dict(arrays))             # health version moved
        assert sched.plan_cache.invalidations == before + 1
        assert not r.stats.plan_cache_hit            # new (CPU-only) slots
        # probation probe succeeds -> reinstatement bumps the version again
        before = sched.plan_cache.invalidations
        sched.run(sct, dict(arrays))                 # probe run (clean)
        sched.run(sct, dict(arrays))
        assert sched.plan_cache.invalidations >= before + 1

    def test_invalidated_on_adjusted_shares(self):
        # an unbalanced balancer forces the "adjusted" action on the
        # recurrent path, which must explicitly invalidate the cache
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               balancer=LoadBalancer(max_dev=1.5, weight=1.0))
        sct, arrays = saxpy_tree(), saxpy_arrays()
        sched.run(sct, dict(arrays))
        before = sched.plan_cache.invalidations
        r = sched.run(sct, dict(arrays))
        assert r.action == "adjusted"
        assert sched.plan_cache.invalidations == before + 1

    def test_disabled_cache_never_hits(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               plan_cache=False)
        sct, arrays = saxpy_tree(), saxpy_arrays()
        for _ in range(3):
            assert not sched.run(sct, dict(arrays)).stats.plan_cache_hit
        assert sched.plan_cache.hits == 0

    def test_capacity_bound(self):
        cache = PlanCache(capacity=2)
        sct = saxpy_tree()
        slots = [ExecutionSlot("cpu0/f0", "cpu")]
        for n in (64, 128, 256):
            cache.partition(sct, {"x": (n,), "y": (n,)}, slots, [1.0])
        assert len(cache._parts) <= 2


# ---------------------------------------------------------------------------
# Persistent pools
# ---------------------------------------------------------------------------

class TestPersistentPool:
    def test_pool_created_once_and_reused(self):
        ex = ThreadedExecutor(policy=POLICY)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        for _ in range(3):
            ex.execute(sct, part, saxpy_arrays(), prof)
        assert ex.pools_created == 1
        assert ex.pool_reuses == 2
        ex.close()

    def test_legacy_flag_restores_per_run_pools(self):
        ex = ThreadedExecutor(policy=POLICY, persistent_pool=False)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        for _ in range(2):
            ex.execute(sct, part, saxpy_arrays(), prof)
        assert ex.pools_created == 0            # legacy path never registers
        assert ex._pool is None

    def test_session_shutdown_closes_executor(self):
        ex = ThreadedExecutor(policy=POLICY)
        sched = make_scheduler(ex)
        with Session(sched) as s:
            s.run(saxpy_tree(), **saxpy_arrays()).get()
        assert ex._pool is None
        assert ex._buffers == {}


# ---------------------------------------------------------------------------
# In-place merge
# ---------------------------------------------------------------------------

class TestInPlaceMerge:
    def test_matches_legacy_concatenate_merge(self):
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        arrays = saxpy_arrays()
        legacy = ThreadedExecutor(policy=POLICY, inplace_merge=False,
                                  persistent_pool=False)
        expected, _ = legacy.execute(sct, part, dict(arrays), prof)
        ex = ThreadedExecutor(policy=POLICY)
        got, _ = ex.execute(sct, part, dict(arrays), prof)
        np.testing.assert_array_equal(np.asarray(expected["z"]),
                                      np.asarray(got["z"]))
        ex.close()

    def test_zero_merge_bytes_once_shape_learned(self):
        ex = ThreadedExecutor(policy=POLICY)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        ex.execute(sct, part, saxpy_arrays(), prof)     # learns shape
        assert ex.last_merge_bytes > 0                  # packing copy
        ex.execute(sct, part, saxpy_arrays(), prof)     # direct writes
        assert ex.last_merge_bytes == 0
        assert ex.last_direct_bytes == 256 * 4
        ex.close()

    def test_outputs_reuse_buffer_across_runs(self):
        ex = ThreadedExecutor(policy=POLICY)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        o1, _ = ex.execute(sct, part, saxpy_arrays(a=2.0), prof)
        z1 = o1["z"]
        o2, _ = ex.execute(sct, part, saxpy_arrays(a=3.0), prof)
        assert o2["z"] is z1        # documented aliasing semantics
        ex.close()

    def test_user_merge_fn_precedence_over_partitionable(self):
        # satellite: a user-supplied merge fn wins even though "z" is a
        # partitionable output that would otherwise be concatenated
        merges = {"z": lambda parts: sum(np.sum(p) for p in parts)}
        ex = ThreadedExecutor(policy=POLICY, merges=merges)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        arrays = saxpy_arrays()
        out, _ = ex.execute(sct, part, dict(arrays), make_profile(sct))
        expected = np.sum(2.0 * arrays["x"] + arrays["y"])
        assert np.isclose(float(out["z"]), float(expected))
        ex.close()

    def test_buffers_dropped_after_timeout(self):
        inj = FaultInjector(stall_on_call={"gpu0": [2]}, stall_seconds=2.0)
        ex = ThreadedExecutor(
            policy=FaultPolicy(watchdog_multiple=1.0, min_deadline=0.2,
                               default_deadline=0.2), injector=inj)
        sct = saxpy_tree()
        part = three_slot_part(sct)
        prof = make_profile(sct)
        ex.execute(sct, part, saxpy_arrays(), prof)
        assert ex._buffers                       # learned + retained
        ex.execute(sct, part, saxpy_arrays(), prof)   # stall -> timeout
        assert any(r.kind == "timeout" for r in ex.last_failures)
        assert ex._buffers == {}                 # hung thread can't corrupt
        ex.close()


# ---------------------------------------------------------------------------
# Partitioned residency
# ---------------------------------------------------------------------------

class TestResidency:
    def expected_v(self, arrays):
        return (2.0 * (2.0 * arrays["x"] + arrays["y"])) + arrays["y"]

    def test_chain_matches_sequential_merge(self):
        arrays = saxpy_arrays()
        legacy = make_scheduler(ThreadedExecutor(
            policy=POLICY, persistent_pool=False, inplace_merge=False),
            plan_cache=False)
        env = dict(arrays)
        for sct in chain_trees():
            env.update({k: np.copy(v) for k, v in
                        legacy.run(sct, env).outputs.items()})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        runs = sched.run_chain(chain_trees(), dict(arrays))
        np.testing.assert_array_equal(
            env["v"], np.copy(np.asarray(runs[-1].outputs["v"])))

    def test_intermediate_steps_stay_resident(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        runs = sched.run_chain(chain_trees(), saxpy_arrays())
        assert [r.stats.resident for r in runs] == [True, True, False]
        assert all(r.stats.merge_bytes == 0 for r in runs[:-1])
        assert runs[0].outputs == {}             # merge skipped entirely

    def test_fault_falls_back_to_full_merge(self):
        arrays = saxpy_arrays()
        inj = FaultInjector(crash_on_call={"gpu0": [1]})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY, injector=inj))
        runs = sched.run_chain(chain_trees(), dict(arrays))
        assert runs[0].stats.retries == 1
        assert not runs[0].stats.resident        # repartitioned -> merged
        np.testing.assert_array_equal(
            self.expected_v(arrays),
            np.copy(np.asarray(runs[-1].outputs["v"])))

    def test_incompatible_partitioning_materializes(self):
        ex = ThreadedExecutor(policy=POLICY)
        sct = saxpy_tree()
        prof = make_profile(sct)
        ex.execute(sct, three_slot_part(sct), saxpy_arrays(), prof,
                   keep_resident=True)
        res = ex.last_resident
        assert res is not None
        other = three_slot_part(sct, shares=(0.25, 0.5, 0.25))
        assert not res.compatible(other)
        merged = res.materialize()
        expected = 2.0 * saxpy_arrays()["x"] + saxpy_arrays()["y"]
        np.testing.assert_array_equal(expected, np.asarray(merged["z"]))
        ex.close()

    def test_simulator_has_no_residency(self):
        from repro.core import SimulatedExecutor
        assert SimulatedExecutor.supports_residency is False

    def test_session_run_chain(self):
        arrays = saxpy_arrays()
        with Session(make_scheduler(ThreadedExecutor(policy=POLICY))) as s:
            runs = s.run_chain(chain_trees(), **arrays).get()
        np.testing.assert_array_equal(
            self.expected_v(arrays),
            np.copy(np.asarray(runs[-1].outputs["v"])))


# ---------------------------------------------------------------------------
# Timing instrumentation
# ---------------------------------------------------------------------------

class TestTimingBreakdown:
    def test_breakdown_populated(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        r = sched.run(saxpy_tree(), saxpy_arrays())
        s = r.stats
        assert s.plan_seconds > 0
        assert s.compute_seconds > 0
        assert s.merge_seconds >= 0
        assert s.overhead_seconds == pytest.approx(
            s.plan_seconds + s.pool_seconds + s.dispatch_seconds
            + s.merge_seconds)

    def test_simulator_reports_timing(self):
        from repro.core import SimDevice, SimulatedExecutor
        ex = SimulatedExecutor([SimDevice("gpu0", "gpu", flops=1e12),
                                SimDevice("cpu0", "cpu", flops=1e11,
                                          cores=4)])
        sched = make_scheduler(ex)
        r = sched.run(saxpy_tree(), saxpy_arrays())
        assert r.stats.merge_bytes == 0
        assert r.stats.compute_seconds > 0


# ---------------------------------------------------------------------------
# Satellite: zero-total share fallback
# ---------------------------------------------------------------------------

class TestZeroShareFallback:
    def test_all_probing_with_zero_probe_share(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        sched.health.probe_share = 0.0
        sched.health.quarantine_after = 1
        sched.health.probe_after = 0
        # quarantine every device, then let them all probe at share 0
        sched.health.record_failure("gpu0")
        sched.health.record_failure("cpu0")
        prof = make_profile(saxpy_tree())
        slots = sched._slots(prof)
        shares = sched._per_slot_shares(prof, slots)   # no ZeroDivisionError
        assert shares == pytest.approx([1.0 / len(slots)] * len(slots))
        assert sum(shares) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Blocked read-back of accelerator outputs
# ---------------------------------------------------------------------------

#: a block size small enough to cut the test's outputs: 1024 rows of 16
#: float32, or 16384 elements of a 1-D output
SMALL_BLOCK = 64 << 10
#: a block size no output reaches: every output read back whole, as one
#: ``dst[...] = v`` per output did
WHOLE = 1 << 40


def read_back_tree():
    import jax.numpy as jnp
    return kernel(lambda x: (jnp.asarray(x) * 2.0 + 1.0,
                             jnp.asarray(x) * -3.0),
                  name="two_maps", inputs=[vector("x")],
                  outputs=[vector("z"), vector("w")])


def read_back_scheduler(n_accel=1, *, reuse_buffers=True, injector=None,
                        telemetry=None):
    """Accelerator-class slots on JAX's CPU device (kind ``accel``, so
    their outputs are ``jax.Array`` values read back into host buffers),
    beside one host slot."""
    import jax
    dev = jax.devices()[0]
    accel = AcceleratorPlatform([DeviceInfo(f"accel{i}", "accel",
                                            jax_device=dev)
                                 for i in range(n_accel)])
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=1),
                        topology={"L2": 1, "NO_FISSION": 1})
    ex = ThreadedExecutor(policy=POLICY, reuse_buffers=reuse_buffers,
                          injector=injector)
    return Scheduler(host=host, accel=accel, executor=ex, kb=KnowledgeBase(),
                     balancer=LoadBalancer(max_dev=0.0), telemetry=telemetry)


def accel_units(run):
    part = run.node_plan.part
    return sum(u for s, u in zip(part.slots, part.units)
               if s.device_type != "cpu")


#: case -> (input shape, scheduler keywords, crash on the accelerator)
READ_BACK_CASES = {
    "rows_3277": ((3277, 16), {}, False),
    "one_d": ((40000,), {}, False),
    "under_one_block": ((3277, 4), {}, False),
    "no_buffer_reuse": ((3277, 16), {"reuse_buffers": False}, False),
    "fault_retry": ((3277, 16), {"n_accel": 2}, True),
}


class TestBlockedReadBack:
    def run_case(self, monkeypatch, block, shape, kw, crash):
        from repro.core import executor
        monkeypatch.setattr(executor, "D2H_BLOCK_BYTES", block)
        x = np.arange(int(np.prod(shape)), dtype=np.float32
                      ).reshape(shape) / 7.0
        # the second request crashes accel0: its units are re-split over
        # the surviving accelerator and the host, and written again
        inj = FaultInjector(crash_on_call={"accel0": [2]}) if crash else None
        sched = read_back_scheduler(injector=inj, **kw)
        runs, outs = [], []
        # the first request learns the output buffers through the merge,
        # the second writes straight into them
        for _ in range(2):
            r = sched.run(read_back_tree(), {"x": x})
            runs.append(r)
            outs.append({k: np.copy(v) for k, v in r.outputs.items()})
        sched.close()
        return x, runs, outs

    @pytest.mark.parametrize("case", sorted(READ_BACK_CASES))
    def test_blocked_write_back_is_bit_identical_to_whole(self, case,
                                                          monkeypatch):
        shape, kw, crash = READ_BACK_CASES[case]
        x, runs, blocked = self.run_case(monkeypatch, SMALL_BLOCK, shape,
                                         kw, crash)
        _, _, whole = self.run_case(monkeypatch, WHOLE, shape, kw, crash)
        assert runs[1].stats.retries == (1 if crash else 0)
        for got, ref in zip(blocked, whole):
            for name in ("z", "w"):
                assert got[name].tobytes() == ref[name].tobytes()
        np.testing.assert_array_equal(blocked[1]["z"], x * 2.0 + 1.0)
        np.testing.assert_array_equal(blocked[1]["w"], x * -3.0)
        cut = case != "under_one_block"
        for r in runs:
            # both outputs of each accelerator segment: cut, or whole
            assert (r.stats.d2h_blocks > 2) == cut

    @pytest.mark.parametrize("block", [SMALL_BLOCK, WHOLE])
    def test_blocks_counted_and_bytes_unchanged(self, block, monkeypatch):
        from repro.core import Telemetry, executor
        monkeypatch.setattr(executor, "D2H_BLOCK_BYTES", block)
        telemetry = Telemetry()
        sched = read_back_scheduler(telemetry=telemetry)
        x = np.ones((3277, 16), np.float32)
        runs = [sched.run(read_back_tree(), {"x": x}) for _ in range(2)]
        sched.close()
        for r in runs:
            u = accel_units(r)
            # 1024 rows of 64 B fill one small block
            per_output = -(-u // 1024) if block == SMALL_BLOCK else 1
            assert r.stats.d2h_blocks == 2 * per_output
            assert r.stats.d2h_bytes == 2 * u * 16 * 4
        snap = telemetry.metrics.snapshot()
        assert snap["d2h_blocks_total"] == sum(r.stats.d2h_blocks
                                               for r in runs)
        assert snap["d2h_bytes_total"] == sum(r.stats.d2h_bytes
                                              for r in runs)
