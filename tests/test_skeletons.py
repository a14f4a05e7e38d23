"""SCT semantics: Pipeline/Loop/Map/MapReduce + scheduler end-to-end
(paper Sec. 2, Fig. 4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AcceleratorPlatform, DeviceInfo, HostPlatform,
                        KernelSpec, KnowledgeBase, Loop, LoopState, Map,
                        MapReduce, MERGE_ADD, Pipeline, Scheduler, Session,
                        ThreadedExecutor, Trait, kernel, scalar, vector)
from repro.core.skeletons import PartitionInfo


def saxpy_tree():
    return kernel(lambda a, x, y: a * x + y, name="saxpy",
                  inputs=[scalar("a"), vector("x"), vector("y")],
                  outputs=[vector("z")])


class TestSkeletons:
    def test_pipeline_depth_first(self):
        k1 = kernel(lambda x: x + 1, name="k1", inputs=[vector("x")],
                    outputs=[vector("m")])
        k2 = kernel(lambda m: m * 3, name="k2", inputs=[vector("m")],
                    outputs=[vector("y")])
        env = Pipeline(k1, k2).apply({"x": jnp.array([1.0, 2.0])})
        np.testing.assert_allclose(env["y"], [6.0, 9.0])

    def test_loop_for(self):
        body = kernel(lambda x: x * 2, name="dbl", inputs=[vector("x")],
                      outputs=[vector("x")])
        loop = Loop(body, LoopState(max_iterations=4))
        env = loop.apply({"x": jnp.array([1.0])})
        assert float(env["x"][0]) == 16.0

    def test_loop_while_with_state(self):
        body = kernel(lambda x: x + 1, name="inc", inputs=[vector("x")],
                      outputs=[vector("x")])
        loop = Loop(body, LoopState(cond=lambda e: e["x"][0] < 10))
        env = loop.apply({"x": jnp.array([0.0])})
        assert float(env["x"][0]) == 10.0

    def test_mapreduce_host_side(self):
        sq = kernel(lambda x: x * x, name="sq", inputs=[vector("x")],
                    outputs=[vector("s")])
        mr = MapReduce(sq, lambda s: jnp.sum(s), out_name="total")
        env = mr.apply({"x": jnp.array([1.0, 2.0, 3.0])})
        assert float(env["total"]) == 14.0

    def test_size_offset_traits(self):
        k = kernel(lambda x, n, off: x * 0 + n + off, name="k",
                   inputs=[vector("x"), scalar("n", trait=Trait.SIZE),
                           scalar("off", trait=Trait.OFFSET)],
                   outputs=[vector("y")])
        env = k.apply({"x": jnp.zeros(8)})
        assert float(env["y"][0]) == 8.0      # size=8, offset=0

    def test_loop_sees_partition_info(self):
        """A slot's partition info reaches kernels inside a Loop and is
        never carried through the loop as an array."""
        body = kernel(lambda x, off: x + off, name="shift",
                      inputs=[vector("x"), scalar("off", trait=Trait.OFFSET)],
                      outputs=[vector("x")])
        info = PartitionInfo(size=4, offset=10)
        for state in (LoopState(max_iterations=2),
                      LoopState(cond=lambda e: e["x"][0] < 20)):
            env = Loop(body, state).apply(
                {"x": jnp.zeros(4), "__partition__": info})
            assert float(env["x"][0]) == 20.0
            assert env["__partition__"] is info

    def test_unique_id_structural(self):
        a = Pipeline(saxpy_tree())
        b = Pipeline(saxpy_tree())
        assert a.unique_id() == b.unique_id()
        assert Map(saxpy_tree()).unique_id() != a.unique_id()


def make_scheduler(**kw):
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=8),
                        topology={"L1": 8, "L2": 4, "L3": 2,
                                  "NO_FISSION": 1})
    accel = AcceleratorPlatform([DeviceInfo("gpu0", "gpu")], max_overlap=4)
    return Scheduler(host=host, accel=accel, executor=ThreadedExecutor(),
                     kb=KnowledgeBase(), **kw)


class TestSchedulerEndToEnd:
    def test_correct_result_any_distribution(self):
        sched = make_scheduler(default_share_a=0.6)
        sct = saxpy_tree()
        x = np.arange(64, dtype=np.float32)
        y = np.ones(64, dtype=np.float32)
        run = sched.run(sct, {"a": np.float32(2.0), "x": x, "y": y})
        np.testing.assert_allclose(run.outputs["z"], 2 * x + y)
        assert run.action in ("derived", "exact")

    def test_recurrent_execution_reuses_profile(self):
        sched = make_scheduler()
        sct = saxpy_tree()
        arrays = {"a": np.float32(1.0),
                  "x": np.ones(32, np.float32),
                  "y": np.zeros(32, np.float32)}
        first = sched.run(sct, arrays)
        second = sched.run(sct, arrays)
        assert second.action in ("reused", "adjusted")

    def test_workload_change_triggers_derivation(self):
        sched = make_scheduler()
        sct = saxpy_tree()
        sched.run(sct, {"a": np.float32(1.0), "x": np.ones(32, np.float32),
                        "y": np.zeros(32, np.float32)})
        run = sched.run(sct, {"a": np.float32(1.0),
                              "x": np.ones(64, np.float32),
                              "y": np.zeros(64, np.float32)})
        assert run.action in ("derived", "exact")
        assert len(sched.kb) >= 2

    def test_session_future(self):
        sched = make_scheduler()
        sess = Session(sched)
        fut = sess.run(saxpy_tree(), a=np.float32(3.0),
                       x=np.ones(16, np.float32),
                       y=np.zeros(16, np.float32))
        out = fut.get(timeout=60)
        np.testing.assert_allclose(out.outputs["z"], 3.0)
        sess.shutdown()

    def test_merge_functions(self):
        sq = kernel(lambda x: jnp.sum(x * x)[None], name="sq",
                    inputs=[vector("x")], outputs=[vector("partial")])
        sched = make_scheduler()
        sched.executor.merges["partial"] = MERGE_ADD
        x = np.arange(16, dtype=np.float32)
        run = sched.run(Map(sq), {"x": x})
        np.testing.assert_allclose(np.asarray(run.outputs["partial"]).sum(),
                                   float((x * x).sum()), rtol=1e-5)
