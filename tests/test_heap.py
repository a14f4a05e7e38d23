"""The process's heap guard (``repro.heap``): when it hands freed heap back,
and that every executor run asks it."""
from __future__ import annotations

import itertools
import threading

import numpy as np
import pytest

from repro import heap
from repro.core import (AcceleratorPlatform, DeviceInfo, HostPlatform,
                        KnowledgeBase, Scheduler, Telemetry,
                        ThreadedExecutor, kernel, vector)

MIB = 1 << 20


def fake_guard(monkeypatch, readings, slack=100 * MIB):
    """A guard whose resident set reads ``readings`` in turn and whose
    release only counts; returns it and the list of releases."""
    trims = []
    it = iter(readings)
    monkeypatch.setattr(heap, "resident_bytes", lambda: next(it))
    guard = heap.HeapGuard(slack)
    guard._trim = trims.append
    return guard, trims


#: case -> (resident readings in MiB, each check's answer); a release
#: reads the resident set once more, after it
GUARD_CASES = {
    "level": ([500, 510, 490, 505, 560], [False] * 5),
    "grows_past_slack": ([500, 550, 600, 300, 350],
                         [False, False, True, False]),
    "floor_follows_down": ([500, 300, 390, 420, 410],
                           [False, False, False, True]),
    "growth_after_release": ([500, 650, 520, 600, 620, 700],
                             [False, True, False, True]),
}


@pytest.mark.parametrize("case", sorted(GUARD_CASES))
def test_the_guard_releases_once_the_resident_set_grows_by_the_slack(
        case, monkeypatch):
    readings, answers = GUARD_CASES[case]
    guard, trims = fake_guard(monkeypatch, [r * MIB for r in readings])
    got = []
    for _ in answers:
        got.append(guard.check())
        for t in threading.enumerate():
            if t.name == "repro-heap-release":
                t.join()
    assert got == answers
    assert len(trims) == guard.releases == sum(answers)
    assert all(t == 0 for t in trims)


def test_no_second_release_while_one_runs(monkeypatch):
    guard, _ = fake_guard(monkeypatch, [0, 200 * MIB, 400 * MIB, 50 * MIB])
    entered, hold = threading.Event(), threading.Event()

    def slow_trim(pad):
        entered.set()
        hold.wait(10)
    guard._trim = slow_trim
    assert not guard.check()
    assert guard.check()
    assert entered.wait(10)
    assert not guard.check()            # busy: no second thread
    hold.set()
    for t in threading.enumerate():
        if t.name == "repro-heap-release":
            t.join()
    assert guard.releases == 1
    assert guard._floor == 50 * MIB     # read after the release


def test_without_malloc_trim_the_guard_does_nothing(monkeypatch):
    monkeypatch.setattr(heap, "resident_bytes",
                        lambda: pytest.fail("read without a trim"))
    guard = heap.HeapGuard(0)
    guard._trim = None
    assert not any(guard.check() for _ in range(3))
    assert guard.releases == 0


def test_the_resident_set_and_malloc_trim_are_found_here():
    rss = heap.resident_bytes()
    assert rss is not None and rss > 0
    assert heap.HeapGuard()._trim is not None


def test_every_executor_run_asks_the_guard(monkeypatch):
    # a resident set that grows by a byte at each reading, and slack 0:
    # the first run sets the floor, every later one releases
    monkeypatch.setattr(heap, "resident_bytes", itertools.count().__next__)
    guard = heap.HeapGuard(0)
    monkeypatch.setattr(heap, "GUARD", guard)
    telemetry = Telemetry()
    sched = Scheduler(
        host=HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=2),
                          topology={"L2": 1, "NO_FISSION": 1}),
        accel=AcceleratorPlatform([DeviceInfo("gpu0", "gpu")]),
        executor=ThreadedExecutor(), kb=KnowledgeBase(),
        telemetry=telemetry)
    sct = kernel(lambda x: x + 1.0, name="inc", inputs=[vector("x")],
                 outputs=[vector("y")])
    x = np.arange(4096, dtype=np.float32)
    for _ in range(3):
        np.testing.assert_array_equal(sched.run(sct, {"x": x}).outputs["y"],
                                      x + 1.0)
        for t in threading.enumerate():
            if t.name == "repro-heap-release":
                t.join()
    sched.close()
    assert guard.releases == 2
    assert telemetry.metrics.snapshot()["heap_releases_total"] == \
        guard.releases
