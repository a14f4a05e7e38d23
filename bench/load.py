"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and drives ``Session.submit`` with it.

A mix is data only:

* ``size``: the request's problem size (image side in pixels, or elements);
* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when the previous one returns) or ``"open"`` (Poisson arrivals at
  ``rate`` requests per second, sent on schedule whatever the system does);
* ``pool``: how many distinct inputs the run makes from its seed; request
  ``k`` carries input ``k % pool``;
* ``sample_every``: one request in so many keeps its outputs for the
  comparison that decides ``correct``, at an offset drawn from the seed,
  and so does the window's first request;
* ``settle``: warm-up until ``quiet`` requests in a row leave the split
  unchanged, for at most ``cap_s`` seconds; an open mix then sends
  ``burst_s`` seconds of its arrivals before the window.

Every seed of an open mix gets the same arrival gaps, in another order, so
the seed changes which input meets which gap and never how much work the
window holds.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

#: seed of the open loop's gap multiset, the same for every run
GAP_SEED = 20151021
#: longest a request of the window may take after the window closes
LATE_S = 60.0


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def input_seed(seed: int, index: int) -> int:
    """Seed of pool entry ``index`` of run ``seed``."""
    return int(np.random.SeedSequence([seed, 2, index]).generate_state(1)[0])


def open_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds after the window opens) of an open-loop window:
    ``round(rate * seconds)`` arrivals with exponential gaps, scaled so that
    the gaps fill the window exactly; the seed permutes the gaps."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0, n)
    gaps = _rng(seed, 0).permutation(gaps)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return starts * (seconds / gaps.sum())


def sample_offset(seed: int, every: int) -> int:
    """Requests ``k`` with ``k % every == offset`` keep their outputs."""
    return int(_rng(seed, 1).integers(every))


@dataclasses.dataclass
class Request:
    """One request of the window, as the client saw it."""

    index: int
    pool: int
    due: float                  # perf_counter when it was due
    submit: float = 0.0         # perf_counter when submit was called
    done: float = 0.0           # perf_counter when its result was ready
    ok: bool = False
    error: str = ""
    span_s: float = 0.0         # the node's execution span (GraphHandle)
    stats: Any = None           # the node's ExecutionStats
    action: str = ""
    accel_units: Dict[str, int] = dataclasses.field(default_factory=dict)
    units: int = 0
    outputs: Optional[Dict[str, np.ndarray]] = None   # kept for the check
    settled: threading.Event = dataclasses.field(
        default_factory=threading.Event)     # set once recorded

    @property
    def latency(self) -> float:
        return self.done - self.due


class Client:
    """Submits one-node graphs of ``sct`` through ``session`` and records
    each request; outputs of sampled requests are kept (copied when the
    executor may reuse their buffers)."""

    def __init__(self, session, sct, pool: List[Dict[str, np.ndarray]], *,
                 sample_every: int, offset: int, copy_outputs: bool):
        from repro.core import JobGraph
        self._job_graph = JobGraph
        self.session = session
        self.sct = sct
        self.pool = pool
        self.every = sample_every
        self.offset = offset
        self.copy_outputs = copy_outputs
        self.keep = False           # True inside the window

    def sampled(self, k: int) -> bool:
        """Whether request ``k`` of the window is one the check compares:
        the first, and one in ``every`` at the seed's offset."""
        return k == 0 or k % self.every == self.offset

    def submit(self, req: Request):
        graph = self._job_graph()
        node = graph.add(self.sct)
        req.submit = time.perf_counter()
        handle = self.session.submit(graph, **self.pool[req.pool])

        def settle(h) -> None:
            req.done = time.perf_counter()
            try:
                self._record(req, h, node)
            except Exception as e:     # recorded, never lost in the handle
                req.ok = False
                req.error = f"record: {type(e).__name__}: {e}"
            finally:
                req.settled.set()
        handle.add_done_callback(settle)
        return handle

    def _record(self, req: Request, handle, node: str) -> None:
        if handle.error is not None:
            req.error = f"{type(handle.error).__name__}: {handle.error}"
            return
        run = handle.runs[node]
        if self.keep and self.sampled(req.index):
            req.outputs = {k: (np.array(v, copy=True) if self.copy_outputs
                               else np.asarray(v))
                           for k, v in run.outputs.items()}
        start, end = handle.spans()[node]
        req.span_s = (end - start) / 1e6
        req.stats = run.stats
        req.action = run.action
        part = run.node_plan.part
        req.units = int(sum(part.units))
        req.accel_units = {}
        for s, u in zip(part.slots, part.units):
            if s.device_type != "cpu":
                req.accel_units[s.device] = \
                    req.accel_units.get(s.device, 0) + int(u)
        # a result that came is a result, even after a slot was retried:
        # the retries show in ``stats``, the answer in the check
        req.ok = True


def closed_loop(client: Client, clients: int, seconds: float
                ) -> List[Request]:
    """``clients`` callers, each submitting its next request when the last
    returns, until ``seconds`` have passed; requests started before the
    window closed run to their end."""
    lock = threading.Lock()
    counter = [0]
    reqs: List[Request] = []
    t_end = time.perf_counter() + seconds

    def caller() -> None:
        while time.perf_counter() < t_end:
            with lock:
                k = counter[0]
                counter[0] += 1
            req = Request(k, k % len(client.pool), time.perf_counter())
            with lock:
                reqs.append(req)
            client.submit(req)
            if not req.settled.wait(LATE_S + seconds):
                req.error, req.done = "timeout", time.perf_counter()
                return

    threads = [threading.Thread(target=caller, name=f"client{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reqs.sort(key=lambda r: r.index)
    return reqs


def open_loop(client: Client, due: np.ndarray) -> Dict[str, Any]:
    """Send request ``k`` at ``t0 + due[k]`` whatever the system does, and
    wait for every one of them (at most ``LATE_S`` past the last due time).
    Returns the requests and how late the generator sent them."""
    t0 = time.perf_counter()
    reqs = [Request(k, k % len(client.pool), t0 + float(d))
            for k, d in enumerate(due)]
    late = []
    for req in reqs:
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - req.due)
        client.submit(req)
    deadline = reqs[-1].due + LATE_S
    for req in reqs:
        if not req.settled.wait(max(0.0, deadline - time.perf_counter())):
            req.error, req.done = "timeout", time.perf_counter()
    return {"requests": reqs, "late_p50_s": float(np.median(late)),
            "late_max_s": float(np.max(late))}

