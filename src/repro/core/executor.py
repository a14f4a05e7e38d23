"""Task launcher / executor — real partitioned execution on this host.

The Scheduler produces a :class:`ConcretePartitioning`; the executor turns
it into a group of tasks (one per execution slot, paper Fig. 2/3), places
them in per-slot work queues (a persistent thread pool here), runs the SCT
over each partition, and merges the partial results:

  * partitionable outputs — assembled along their partition dimension
    (the partitions tile the domain, paper Sec. 3.1);
  * COPY / replicated outputs — taken from the first slot;
  * reduced outputs — combined with the kernel-declared or user-supplied
    *merging function* (paper Sec. 3.4; MERGE_ADD & friends).

``Size`` / ``Offset`` traits are bound per-slot through the environment's
``__partition__`` entry.

Locality / zero-copy pipeline
-----------------------------
Recurrent runs of the same (SCT, workload) are the serving-loop regime the
paper's data-locality results target, so the hot path amortises every
per-dispatch cost:

  * **persistent worker pool** — created once, reused across runs and
    retry attempts, torn down by :meth:`ThreadedExecutor.close` (called
    from ``Session.shutdown``).  The pool is only re-created after a
    watchdog timeout, since a hung thread can never be reclaimed.
  * **zero-copy segment environments** — per-slot input slices are numpy
    views into the caller's arrays, never copies.
  * **in-place merge** — partitionable outputs are written by each slot
    directly into a preallocated, shape-keyed output buffer that is
    reused across runs; the merge phase then copies zero bytes.  The
    first run of a new output shape falls back to one packing copy while
    the buffer is learned.  *Consequence*: the arrays returned by one
    ``execute`` are overwritten by the next run on the same executor —
    callers that retain outputs across runs must copy them (or construct
    the executor with ``reuse_buffers=False``).
  * **overlapped read-back** — an accelerator slot starts the
    device→host copy of every output it writes into a buffer as soon as
    its program returns, all at once (``jax.Array.copy_to_host_async``);
    an output over ``D2H_BLOCK_BYTES`` is first cut on its device into
    row blocks of whole (8, 128) tiles, so several copies and host
    relayouts run together.  The slot then writes the blocks into their
    rows in order, each through ``np.asarray``, while the later ones are
    still in flight.  The merge's copies of accelerator outputs take the
    same path.
  * **partitioned residency** — ``execute(..., keep_resident=True)``
    skips the merge entirely and hands back a :class:`ResidentPartition`
    whose slot-local outputs feed the next SCT's slot-local inputs
    (``execute(..., resident=...)``), eliminating the merge→re-split
    round trip between the kernels of a compound chain (the paper's
    inter-kernel locality rule).  Whenever the next run's partitioning
    differs — other slots/shares, other partition dims or epu, or a
    fault-repartitioned layout — the handle transparently *materialises*
    (full merge) and the run proceeds on the safe path.

Merge precedence (per output name): 1. a user-supplied merge function in
``ThreadedExecutor.merges`` — honoured even when the output is also
partitionable; 2. in-place assembly along the partition dim for
partitionable outputs; 3. first slot's value for COPY / scalar outputs.
Direct slot writes assume deterministic kernels (a timed-out slot retried
elsewhere re-produces the same bytes); merged results are bit-identical
to the historical ``np.concatenate`` merge.

Compiled bodies and device binding
----------------------------------
Every slot runs its segment through ``sct.compiled_apply``: the SCT's
whole body is one ``jax.jit`` kept on the SCT object, compiled once per
(SCT object, device, segment shapes and dtypes) — a recurrent request
reuses the program, and so does another offset of the same shape, since
the OFFSET trait is a traced scalar while SIZE stays a static int.  No
slot runs the tree op by op.  Registry counters
``sct_programs_compiled_total`` (a run that traced and compiled a new
program) and ``sct_program_runs_total`` (every slot run) say how often
that happens, and each ``compute`` span notes ``compiled``.
A slot whose device (``ExecutionSlot.info.jax_device``) is set runs its
program under ``jax.default_device`` of that device — a thread-local
setting, so concurrent slots do not interfere — and computes there.
Only the compute moves: inputs still arrive as host views, and outputs
are merged into host buffers.  With telemetry on, each ``slot`` span
notes ``bound`` (the slot's device) and ``placed`` (the devices that hold
its outputs).

What crosses between host and accelerator is counted on every run, as
bytes handed over (not as DMA transfers, which JAX performs and may
split, pad or skip): ``h2d_bytes`` sums the host-resident values (NumPy
arrays and NumPy scalars) handed to accelerator-class slots, and
``d2h_bytes`` the accelerator-class outputs (``jax.Array``) read into
host memory by a slot's direct write or by the merge's copies (values a
user merge function combines, COPY outputs handed back as they are and
what a resident chain keeps on the slots are not counted).  The counts
are taken where the slot receives its values and where its outputs are
written back, so a copy made anywhere else (an explicit ``device_put``
before the slot, a read-back by the caller) is not in them.  Cutting an
output into blocks changes none of these bytes; ``d2h_blocks`` counts
the device→host copies the read-back started (one per output read
whole, one per block of a cut one).

Each ``slot`` span (``cls="a"`` for accelerator-class slots, ``"b"`` for
the host class) has two children, also timed on every run: ``compute``
(segment environment, the compiled program with the implicit upload of
host inputs, the start of the read-back and ``block_until_ready``; it
notes ``compiled``) and
``writeback`` (waiting for the read-back's copies and writing them into
host buffers; it notes ``blocks``, the copies).  The run's
``compute_a`` / ``writeback_a`` are those of the accelerator slot with
the longest time.

At the end of every run the process's :data:`repro.heap.GUARD` reads the
resident set and, once it has grown by ``repro.heap.SLACK_BYTES``, hands
freed heap back to the system on a thread of its own (counter
``heap_releases_total``): under ``jax.profiler`` the host slot's freed
XLA:CPU buffers would otherwise stay resident, about 128 MiB a 4096² px
filter request.

Failure semantics
-----------------
Execution is tracked per *segment* — a contiguous domain-unit range bound
to one slot (initially one segment per slot).  A slot that raises is
contained: its exception becomes a :class:`~repro.core.faults.FaultRecord`
instead of crashing the run, the slot is considered dead for the rest of
the request, and its segment is re-split across the surviving slots and
retried (bounded by :class:`~repro.core.faults.FaultPolicy.max_attempts`).
A per-slot watchdog deadline — ``watchdog_multiple x profile.best_time``
— declares stalled slots hung (:class:`~repro.core.faults.SlotTimeout`
semantics; note a hung *thread* cannot be killed in Python, only
abandoned — the persistent pool and the output buffers are retired after
a timeout so an abandoned thread can never touch a later run's state).
When retries are exhausted or no slot survives, a terminal
:class:`~repro.core.faults.ExecutionError` carries the full per-slot
fault history.  Because retried segments tile the lost unit range in
domain order, merged outputs are bit-identical to the fault-free result
for concatenated outputs, and identical for associative merge functions.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import dataclasses
import functools
import threading
import time
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

import jax
import numpy as np

from repro import heap
from repro.core.decomposition import ConcretePartitioning
from repro.core.faults import (ExecutionError, FaultInjector, FaultPolicy,
                               FaultRecord, InjectedFault, split_units)
from repro.core.graph import GraphHandle, GraphResult, JobGraph
from repro.core.knowledge_base import Profile
from repro.core.skeletons import SCT, PartitionInfo
from repro.core.spec import ArgSpec, MergeFn, Transfer, Workload
from repro.core.telemetry import NULL_TELEMETRY, Telemetry

#: An accelerator output larger than this many bytes is read back in row
#: blocks of about this size, so that their copies run at once.  On a v5e
#: chip 8 MiB blocks read the filter's 54 MB outputs back in half the time
#: whole outputs take, as fast as 4 MiB with half the copies, while 2 MiB
#: costs more to start than it saves (PERF.md, Findings PR 14)
D2H_BLOCK_BYTES = 8 << 20
#: rows of a TPU (8, 128) tile, and elements of a 1-D array's tile
_TILE_ROWS = 8
_TILE_ELEMS = 8 * 128


def output_spec(sct: SCT, name: str) -> Optional[ArgSpec]:
    for leaf in sct.leaves():
        for a in leaf.spec.outputs:
            if a.name == name:
                return a
    return None


@dataclasses.dataclass
class ExecResult:
    """Everything one ``execute`` call produced, as a per-call value.

    Concurrent graph nodes share one executor, so per-call results must
    travel with the call instead of through mutable ``last_*`` fields
    (which remain, updated by :meth:`ThreadedExecutor.execute`, for
    sequential callers and older integrations).
    """

    outputs: Dict[str, Any]
    times: List[float]                      # per-slot busy seconds
    failures: List[FaultRecord]
    retries: int
    timing: Dict[str, float]                # pool/compute/merge/dispatch
    merge_bytes: int
    direct_bytes: int
    resident: Optional["ResidentPartition"]
    n_a: int                                # accelerator-class slot count
    h2d_bytes: int = 0                      # host values to class-a slots
    d2h_bytes: int = 0                      # class-a outputs read to host
    d2h_blocks: int = 0                     # device→host copies started
    compute_a: float = 0.0                  # slowest class-a slot: compute
    writeback_a: float = 0.0                # ... and its write-back


@dataclasses.dataclass
class _SlotResult:
    outputs: Dict[str, Any]
    seconds: float
    written: frozenset = frozenset()    # outputs direct-written to buffers
    h2d_bytes: int = 0                  # host values handed to the slot
    d2h_bytes: int = 0                  # outputs direct-written to host
    d2h_blocks: int = 0                 # device→host copies it started
    compute_s: float = 0.0              # segment env, program, ready
    writeback_s: float = 0.0            # direct write to host buffers


@dataclasses.dataclass
class _Segment:
    """A contiguous domain-unit range assigned to one execution slot."""

    slot: int                   # index into part.slots
    start: int                  # domain-unit offset of the range
    units: int                  # domain units in the range


@dataclasses.dataclass
class _OutputTarget:
    """Preallocated destination for one partitionable output."""

    buffer: np.ndarray
    axis: int
    epu: int


@dataclasses.dataclass
class ResidentPartition:
    """Slot-resident outputs of one SCT run over a concrete partitioning.

    Holds one environment per realised segment, restricted to produced
    (and inherited) vector names, so a back-to-back run over the *same*
    domain decomposition can consume them slot-locally without the
    merge→re-split round trip.  ``meta`` records each resident vector's
    ``(partition_dim, epu)``; ``extras`` carries non-partitionable
    results (reduced / COPY / user-merged outputs and values carried
    forward from earlier chain steps) as whole arrays.

    ``compatible`` gates the zero-copy handoff; on any mismatch the
    consumer calls :meth:`materialize` and falls back to the full-merge
    path, so chaining is never less correct than merging.
    """

    part: ConcretePartitioning
    layout: Tuple[Tuple[int, int], ...]     # realised (start, units) ranges
    envs: List[Dict[str, Any]]              # slot-local arrays per segment
    meta: Dict[str, Tuple[int, int]]        # name -> (axis, epu)
    extras: Dict[str, Any]                  # whole-array results
    executor: "ThreadedExecutor"
    sct: SCT

    def __post_init__(self) -> None:
        self._index = {rng: i for i, rng in enumerate(self.layout)}

    # -- zero-copy handoff --------------------------------------------------
    def compatible(self, part: ConcretePartitioning) -> bool:
        """True when ``part`` can consume the resident data slot-locally."""
        if not self.part.same_layout(part):
            return False
        if self.layout != part.layout():
            return False                    # fault-repartitioned realisation
        for name, (axis, epu) in self.meta.items():
            vp = part.plan.vectors.get(name)
            if vp is None:
                continue                    # next SCT does not touch it
            if vp.copy or vp.partition_dim != axis or vp.epu != epu:
                return False
        return True

    def segment_env(self, start: int, units: int) -> Dict[str, Any]:
        """Slot-local resident values covering one segment range.

        Exact layout matches return the stored environment; sub-ranges —
        the fault path re-splits a lost segment across survivors — are
        served as views into the covering segment's arrays, so retries
        stay zero-copy and bit-identical."""
        i = self._index.get((start, units))
        if i is not None:
            return self.envs[i]
        for (s0, u0), j in self._index.items():
            if s0 <= start and start + units <= s0 + u0:
                out: Dict[str, Any] = {}
                for name, v in self.envs[j].items():
                    axis, epu = self.meta[name]
                    off = (start - s0) * epu
                    idx = [slice(None)] * v.ndim
                    idx[axis] = slice(off, off + units * epu)
                    out[name] = v[tuple(idx)]
                return out
        return {}

    # -- introspection ------------------------------------------------------
    def names(self) -> List[str]:
        seen = dict.fromkeys(self.meta)
        seen.update(dict.fromkeys(self.extras))
        return list(seen)

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Global (merged) shapes of every resident vector."""
        out: Dict[str, Tuple[int, ...]] = {}
        for name, (axis, _) in self.meta.items():
            parts = [e[name] for e in self.envs if name in e]
            if not parts:
                continue
            shape = list(np.shape(parts[0]))
            shape[axis] = sum(int(np.shape(p)[axis]) for p in parts)
            out[name] = tuple(shape)
        for name, v in self.extras.items():
            if hasattr(v, "shape"):
                out[name] = tuple(v.shape)
        return out

    # -- safe fallback ------------------------------------------------------
    def materialize(self) -> Dict[str, Any]:
        """Full merge of the resident outputs (the safe fallback)."""
        merged, _ = self.materialize_counted()
        return merged

    def materialize_counted(self) -> Tuple[Dict[str, Any], int]:
        # assemble along each vector's own recorded axis (never via the
        # current SCT's specs — carried vectors may not appear in them)
        merged: Dict[str, Any] = {}
        nbytes = 0
        for name, (axis, _) in self.meta.items():
            parts = [e[name] for e in self.envs if name in e]
            if not parts:
                continue
            out = np.concatenate(
                [p if isinstance(p, np.ndarray) else np.asarray(p)
                 for p in parts], axis=axis)
            merged[name] = out
            nbytes += out.nbytes
        merged.update(self.extras)
        return merged, nbytes


class ThreadedExecutor:
    """Executes SCT partitions on host threads and times each slot.

    Each slot runs the SCT's body as one compiled program per (SCT
    object, device, segment shapes and dtypes), with the partition's
    OFFSET traced and its SIZE static (module docstring); there is no
    op-by-op path.

    ``injector`` (optional) deterministically injects crashes/stalls for
    fault-tolerance experiments; ``policy`` bounds the retry ladder and
    derives the watchdog deadline (see module docstring).

    ``persistent_pool`` / ``inplace_merge`` / ``reuse_buffers`` gate the
    locality optimisations; all default on.  Disabling them restores the
    historical per-attempt pool and ``np.concatenate`` merge — useful as
    the baseline leg of ``benchmarks/locality.py`` and for callers that
    must retain outputs across runs without copying.
    """

    supports_residency = True

    def __init__(self, *, merges: Optional[Dict[str, MergeFn]] = None,
                 max_workers: Optional[int] = None,
                 injector: Optional[FaultInjector] = None,
                 policy: FaultPolicy = FaultPolicy(),
                 persistent_pool: bool = True,
                 inplace_merge: bool = True,
                 reuse_buffers: bool = True,
                 telemetry: Optional[Telemetry] = None):
        self.telemetry = telemetry or NULL_TELEMETRY
        self.merges = dict(merges or {})
        self.max_workers = max_workers
        self.injector = injector
        self.policy = policy
        self.persistent_pool = persistent_pool
        self.inplace_merge = inplace_merge
        self.reuse_buffers = reuse_buffers
        self._last_times: List[float] = []
        self._last_n_a: int = 0
        self.last_failures: List[FaultRecord] = []
        self.last_retries: int = 0
        self.last_timing: Dict[str, float] = {}
        self.last_merge_bytes: int = 0
        self.last_direct_bytes: int = 0
        self.last_resident: Optional[ResidentPartition] = None
        self.pools_created: int = 0
        self.pool_reuses: int = 0
        self._pool: Optional[cf.ThreadPoolExecutor] = None
        self._pool_size: int = 0
        self._queues: Dict[str, cf.ThreadPoolExecutor] = {}
        self._queue_lock = threading.Lock()
        self._buf_lock = threading.Lock()
        self._inuse: set = set()            # id() of buffers leased to a run
        self._buffers: Dict[Tuple[str, Tuple[int, ...], str], np.ndarray] = {}
        self._out_shapes: Dict[Tuple[str, str],
                               Tuple[Tuple[int, ...], np.dtype]] = {}

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Tear down pools / work queues and drop reusable buffers.

        Idempotent: a second ``close`` (double ``Session.shutdown``, a
        context-manager exit after an explicit shutdown) is a no-op."""
        self._retire_pool()
        self._retire_queues()
        with self._buf_lock:
            self._buffers = {}
            self._inuse = set()
        self._out_shapes = {}

    def _retire_pool(self) -> None:
        if self._pool is not None:
            # abandon hung threads instead of joining them (a stalled slot
            # must not block shutdown or the retry round)
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
            self._pool_size = 0

    def _retire_queues(self, devices: Optional[Sequence[str]] = None) -> None:
        """Retire all per-device work queues, or just the given devices
        (a hung slot taints only its own device's queue)."""
        with self._queue_lock:
            names = list(self._queues) if devices is None \
                else [d for d in devices if d in self._queues]
            doomed = [self._queues.pop(d) for d in names]
        for q in doomed:
            q.shutdown(wait=False, cancel_futures=True)

    def _acquire_pool(self, n: int) -> cf.ThreadPoolExecutor:
        if self._pool is not None and self._pool_size < n:
            self._retire_pool()
        if self._pool is None:
            self._pool = cf.ThreadPoolExecutor(max_workers=n)
            self._pool_size = n
            self.pools_created += 1
        else:
            self.pool_reuses += 1
        return self._pool

    def _acquire_queues(self, devices: Sequence[str]
                        ) -> Dict[str, cf.ThreadPoolExecutor]:
        """Per-device work queues (paper Fig. 2): one single-worker pool
        per execution-slot device, shared by every concurrent run.  Two
        segments bound to the same device serialise in its queue;
        segments on disjoint devices genuinely overlap — including
        segments of *different* graph nodes."""
        created = False
        with self._queue_lock:
            for d in devices:
                if d not in self._queues:
                    self._queues[d] = cf.ThreadPoolExecutor(
                        max_workers=1,
                        thread_name_prefix=f"wq-{d.replace('/', '-')}")
                    created = True
            qmap = {d: self._queues[d] for d in devices}
        if created:
            self.pools_created += 1
        else:
            self.pool_reuses += 1
        return qmap

    # -- Scheduler interface -------------------------------------------------
    def execute(self, sct: SCT, part: ConcretePartitioning,
                arrays: Dict[str, Any], profile: Profile, *,
                resident: Optional[ResidentPartition] = None,
                keep_resident: bool = False
                ) -> Tuple[Dict[str, Any], List[float]]:
        """Sequential-caller facade: runs and publishes the ``last_*``
        observation fields (not safe under concurrent callers — those go
        through :meth:`execute_result`)."""
        res = self.execute_result(sct, part, arrays, profile,
                                  resident=resident,
                                  keep_resident=keep_resident)
        self._last_times = res.times
        self._last_n_a = res.n_a
        self.last_failures = res.failures
        self.last_retries = res.retries
        self.last_timing = res.timing
        self.last_merge_bytes = res.merge_bytes
        self.last_direct_bytes = res.direct_bytes
        self.last_resident = res.resident
        return res.outputs, res.times

    def execute_result(self, sct: SCT, part: ConcretePartitioning,
                       arrays: Dict[str, Any], profile: Profile, *,
                       resident: Optional[ResidentPartition] = None,
                       keep_resident: bool = False,
                       request: Optional[str] = None) -> ExecResult:
        """Execute one partitioned run and return a per-call result.

        Thread-safe: concurrent graph nodes share the per-device work
        queues and the buffer pool (leased per call), and nothing about
        this call is observed through shared mutable state.  ``request``
        (the graph request id) labels every span of the call."""
        with self.telemetry.tracer.span(
                "dispatch", request=request, sct=sct.unique_id(),
                slots=len(part.slots), keep_resident=keep_resident) as sp:
            res = self._execute(
                sct, part, arrays, profile, resident=resident,
                keep_resident=keep_resident, request=request)
            sp.note(retries=res.retries,
                    merge_bytes=res.merge_bytes,
                    resident=res.resident is not None)
            return res

    def _execute(self, sct: SCT, part: ConcretePartitioning,
                 arrays: Dict[str, Any], profile: Profile, *,
                 resident: Optional[ResidentPartition] = None,
                 keep_resident: bool = False,
                 request: Optional[str] = None) -> ExecResult:
        leases: List[np.ndarray] = []   # buffers leased to this call
        try:
            return self._execute_leased(sct, part, arrays, profile, leases,
                                        resident=resident,
                                        keep_resident=keep_resident,
                                        request=request)
        finally:
            # end of the run releases its buffer leases: the *next* run may
            # overwrite the returned arrays (the documented aliasing
            # contract), but a *concurrent* run never shares them
            if leases:
                with self._buf_lock:
                    for b in leases:
                        self._inuse.discard(id(b))
            # the host slot's freed XLA:CPU buffers can stay resident
            # (under a profiler): hand them back once they add up
            if heap.GUARD.check():
                self.telemetry.metrics.counter("heap_releases_total").inc()

    def _execute_leased(self, sct: SCT, part: ConcretePartitioning,
                        arrays: Dict[str, Any], profile: Profile,
                        leases: List[np.ndarray], *,
                        resident: Optional[ResidentPartition] = None,
                        keep_resident: bool = False,
                        request: Optional[str] = None) -> ExecResult:
        t_run0 = time.perf_counter()
        pool_sec = [0.0]                # mutable: charged by _run_attempt
        merge_bytes = 0
        deadline = self.policy.deadline(getattr(profile, "best_time", None))

        inherited_extras: Dict[str, Any] = {}
        if resident is not None:
            if resident.compatible(part):
                inherited_extras.update(resident.extras)
            else:
                # safe fallback: partition dims / shares / layout differ
                materialized, nbytes = resident.materialize_counted()
                merge_bytes += nbytes
                inherited_extras.update(materialized)
                arrays = {**arrays, **materialized}
                resident = None

        segments = [_Segment(slot=j, start=s, units=u)
                    for j, (s, u) in enumerate(part.layout())]

        targets: Dict[str, _OutputTarget] = {}
        if self.inplace_merge and not keep_resident:
            targets = self._output_targets(sct, part, leases)

        records: List[FaultRecord] = []
        retries = 0
        dead: set = set()
        done: List[Tuple[_Segment, _SlotResult]] = []
        per_slot_seconds = [0.0] * len(part.slots)
        per_slot_phases = [[0.0, 0.0] for _ in part.slots]

        tel = self.telemetry
        attempts_seconds = 0.0
        pending = segments
        for attempt in range(self.policy.max_attempts):
            t_a0 = time.perf_counter()
            with tel.tracer.span("attempt", request=request,
                                 attempt=attempt,
                                 segments=len(pending)) as att_span:
                outcomes = self._run_attempt(sct, part, arrays, pending,
                                             deadline, attempt, resident,
                                             targets, pool_sec, request)
                attempts_seconds += time.perf_counter() - t_a0
                failed: List[_Segment] = []
                for seg, res in zip(pending, outcomes):
                    per_slot_seconds[seg.slot] += res.seconds
                    if isinstance(res, FaultRecord):
                        records.append(res)
                        dead.add(seg.slot)
                        failed.append(seg)
                        tel.metrics.counter("faults_total",
                                            kind=res.kind).inc()
                        tel.events.emit(
                            "fault", level="warning", message=res.message,
                            device=res.device, fault_kind=res.kind,
                            attempt=res.attempt, slot=res.slot)
                    else:
                        done.append((seg, res))
                        per_slot_phases[seg.slot][0] += res.compute_s
                        per_slot_phases[seg.slot][1] += res.writeback_s
                att_span.note(faults=len(failed))
            lost = [s for s in failed if s.units > 0]
            if not lost:
                break
            alive = [j for j in range(len(part.slots)) if j not in dead]
            if not alive:
                raise ExecutionError(
                    "partition lost: no surviving execution slot can adopt "
                    f"{sum(s.units for s in lost)} domain units",
                    records, attempt + 1)
            if attempt == self.policy.max_attempts - 1:
                raise ExecutionError(
                    f"retries exhausted after {self.policy.max_attempts} "
                    "attempts", records, attempt + 1)
            # re-split each lost range across the surviving slots, in
            # domain order, so the merged result stays bit-identical
            pending = []
            for seg in lost:
                counts = split_units(seg.units, len(alive))
                start = seg.start
                for j, u in zip(alive, counts):
                    if u:
                        pending.append(_Segment(slot=j, start=start, units=u))
                        start += u
            retries += 1
            tel.events.emit("retry.repartition",
                            lost_units=sum(s.units for s in lost),
                            survivors=len(alive), attempt=attempt)

        if any(r.kind == "timeout" for r in records):
            # an abandoned hung thread may still write into the current
            # buffers — retire them so later runs get untainted memory
            with self._buf_lock:
                self._buffers = {}
            tel.events.emit("buffers.dropped", level="warning",
                            message="output buffers retired after a slot "
                                    "timeout (hung-thread containment)")

        done.sort(key=lambda sr: sr[0].start)
        clean = retries == 0 and not records
        t_m0 = time.perf_counter()
        resident_out: Optional[ResidentPartition] = None
        direct_bytes = 0
        d2h_bytes = sum(res.d2h_bytes for _, res in done)
        d2h_blocks = sum(res.d2h_blocks for _, res in done)
        if keep_resident and clean:
            with tel.tracer.span("resident-handoff", request=request,
                                 segments=len(done)):
                resident_out = self._make_resident(
                    sct, part, done, resident, inherited_extras)
            outputs: Dict[str, Any] = {}
        else:
            with tel.tracer.span("merge", request=request) as merge_span:
                outputs, copied, direct_bytes, read_back, blocks = \
                    self._merge(sct, part, done, targets, leases)
                merge_span.note(merge_bytes=copied)
            merge_bytes += copied
            d2h_bytes += read_back
            d2h_blocks += blocks
            if inherited_extras and keep_resident:
                # chain fallback: surface carried values with the merge
                outputs = {**inherited_extras, **outputs}
        merge_seconds = time.perf_counter() - t_m0

        times = per_slot_seconds
        total = time.perf_counter() - t_run0
        compute = max(attempts_seconds - pool_sec[0], 0.0)
        timing = {
            "pool": pool_sec[0],
            "compute": compute,
            "merge": merge_seconds,
            "dispatch": max(total - attempts_seconds - merge_seconds, 0.0),
        }
        n_a = sum(1 for s in part.slots if s.device_type != "cpu")
        # the phases of the accelerator slot that sets time_a
        compute_a, writeback_a = (
            per_slot_phases[max(range(n_a), key=times.__getitem__)]
            if n_a else (0.0, 0.0))
        return ExecResult(
            outputs=outputs, times=times, failures=records, retries=retries,
            timing=timing, merge_bytes=merge_bytes,
            direct_bytes=direct_bytes, resident=resident_out, n_a=n_a,
            h2d_bytes=sum(res.h2d_bytes for _, res in done),
            d2h_bytes=d2h_bytes, d2h_blocks=d2h_blocks,
            compute_a=compute_a, writeback_a=writeback_a)

    def _run_attempt(self, sct: SCT, part: ConcretePartitioning,
                     arrays: Dict[str, Any], segments: Sequence[_Segment],
                     deadline: Optional[float], attempt: int,
                     resident: Optional[ResidentPartition] = None,
                     targets: Optional[Dict[str, _OutputTarget]] = None,
                     pool_sec: Optional[List[float]] = None,
                     request: Optional[str] = None
                     ) -> List[Union[_SlotResult, FaultRecord]]:
        """Run one round of segments concurrently, containing all faults."""
        targets = targets or {}
        pool_sec = pool_sec if pool_sec is not None else [0.0]
        tracer = self.telemetry.tracer
        metrics = self.telemetry.metrics
        traced = tracer.enabled
        produced = _produced_names(sct) if traced else []

        def work(seg: _Segment) -> Union[_SlotResult, FaultRecord]:
            slot = part.slots[seg.slot]
            accel = slot.device_type != "cpu"
            cls = "a" if accel else "b"
            t0 = time.perf_counter()
            with tracer.span(
                    "slot", request=request, cls=cls, device=slot.device,
                    units=seg.units, offset=seg.start,
                    attempt=attempt) as sp:
                try:
                    if self.injector is not None:
                        kind = self.injector.decide(slot.device)
                        if kind == "crash":
                            raise InjectedFault(
                                f"injected crash on {slot.device}")
                        if kind == "stall":
                            time.sleep(self.injector.stall_seconds)
                    dev = slot.info.jax_device if slot.info else None
                    t_c = time.perf_counter()
                    with tracer.span("compute", request=request,
                                     cls=cls) as csp:
                        env = self._segment_env(part, arrays, seg, resident)
                        with (jax.default_device(dev) if dev is not None
                              else contextlib.nullcontext()):
                            out_env, compiled = sct.compiled_apply(env)
                            # every copy back to the host starts now,
                            # before the wait for the outputs
                            reads = {n: _ReadBack(out_env[n]) for n in targets
                                     if accel and _readable(out_env.get(n))}
                            for v in out_env.values():
                                if hasattr(v, "block_until_ready"):
                                    v.block_until_ready()
                        csp.note(compiled=compiled)
                    t_c1 = time.perf_counter()
                    metrics.counter("sct_program_runs_total").inc()
                    if compiled:
                        metrics.counter("sct_programs_compiled_total").inc()
                    if traced:
                        sp.note(bound=None if dev is None else str(dev),
                                placed=_placement(out_env, produced))
                    t_w = time.perf_counter()
                    with tracer.span("writeback", request=request,
                                     cls=cls) as wsp:
                        written = self._direct_write({**out_env, **reads},
                                                     seg, targets)
                        blocks = sum(len(r.blocks) for r in reads.values())
                        wsp.note(blocks=blocks)
                    t1 = time.perf_counter()
                    return _SlotResult(
                        out_env, t1 - t0, written,
                        h2d_bytes=_host_bytes(env.values()) if accel else 0,
                        d2h_bytes=_device_bytes(out_env[n] for n in written)
                        if accel else 0,
                        d2h_blocks=blocks,
                        compute_s=t_c1 - t_c, writeback_s=t1 - t_w)
                except Exception as e:   # containment: never crosses the slot
                    sp.note(fault=type(e).__name__)
                    return FaultRecord(
                        slot=seg.slot, device=slot.device,
                        device_type=slot.device_type, kind="crash",
                        attempt=attempt,
                        message=f"{type(e).__name__}: {e}",
                        seconds=time.perf_counter() - t0)

        if deadline is None and len(segments) == 1:
            return [work(segments[0])]

        # three dispatch modes: per-device work queues (default), one
        # shared persistent pool (explicit max_workers), per-run pool
        # (persistent_pool=False, the historical baseline)
        use_queues = self.persistent_pool and self.max_workers is None
        t0 = time.perf_counter()
        pool: Optional[cf.ThreadPoolExecutor] = None
        if use_queues:
            qmap = self._acquire_queues(
                list(dict.fromkeys(part.slots[seg.slot].device
                                   for seg in segments)))
        elif self.persistent_pool:
            pool = self._acquire_pool(self.max_workers)
        else:
            pool = cf.ThreadPoolExecutor(
                max_workers=self.max_workers or max(len(segments), 1))
        pool_sec[0] += time.perf_counter() - t0
        hung: set = set()
        try:
            if use_queues:
                futs = {qmap[part.slots[seg.slot].device].submit(work, seg): i
                        for i, seg in enumerate(segments)}
            else:
                futs = {pool.submit(work, seg): i
                        for i, seg in enumerate(segments)}
            done_f, hung = cf.wait(futs, timeout=deadline)
            outcomes: List[Union[_SlotResult, FaultRecord]] = \
                [None] * len(segments)  # type: ignore[list-item]
            for f in done_f:
                outcomes[futs[f]] = f.result()
            for f in hung:
                seg = segments[futs[f]]
                slot = part.slots[seg.slot]
                f.cancel()
                outcomes[futs[f]] = FaultRecord(
                    slot=seg.slot, device=slot.device,
                    device_type=slot.device_type, kind="timeout",
                    attempt=attempt,
                    message=f"watchdog: no completion within {deadline:.3f}s",
                    seconds=float(deadline or 0.0))
            return outcomes
        finally:
            # abandon hung threads instead of joining them (a stalled
            # slot must not block the retry round); a tainted persistent
            # pool / device queue is recreated on next acquisition
            if use_queues:
                if hung:
                    self._retire_queues(
                        {part.slots[segments[futs[f]].slot].device
                         for f in hung})
            elif not self.persistent_pool:
                pool.shutdown(wait=False, cancel_futures=True)
            elif hung:
                self._retire_pool()

    def _segment_env(self, part: ConcretePartitioning, arrays: Dict[str, Any],
                     seg: _Segment,
                     resident: Optional[ResidentPartition] = None
                     ) -> Dict[str, Any]:
        """Per-segment environment: slice every partitionable vector to the
        segment's unit range (each slice a zero-copy view, with its own
        epu); replicate the rest.  Resident slot-local values, when
        given, shadow both and skip the slicing entirely."""
        plan = part.plan
        env: Dict[str, Any] = {}
        res_env: Optional[Dict[str, Any]] = None
        source = arrays
        if resident is not None:
            res_env = resident.segment_env(seg.start, seg.units)
            if resident.extras:
                source = {**arrays, **resident.extras}
        for name, arr in source.items():
            if res_env is not None and name in res_env:
                continue
            vp = plan.vectors.get(name)
            if vp is None or vp.copy:
                env[name] = arr
                continue
            off = seg.start * vp.epu
            size = seg.units * vp.epu
            idx = [slice(None)] * arr.ndim
            idx[vp.partition_dim] = slice(off, off + size)
            env[name] = arr[tuple(idx)]     # view, not a copy
        if res_env:
            env.update(res_env)
        witness = next((v for v in plan.vectors.values() if not v.copy), None)
        if witness is not None:
            env["__partition__"] = PartitionInfo(
                size=seg.units * witness.epu,
                offset=seg.start * witness.epu)
        return env

    def last_class_times(self) -> Tuple[float, float]:
        n_a = self._last_n_a
        t = self._last_times
        ta = max(t[:n_a]) if n_a else 0.0
        tb = max(t[n_a:]) if len(t) > n_a else 0.0
        return ta, tb

    def synthesise_arrays(self, sct: SCT, workload: Workload
                          ) -> Dict[str, Any]:
        """Random arrays matching a workload (Algorithm 1 evaluations)."""
        rng = np.random.default_rng(0)
        out: Dict[str, Any] = {}
        for a in sct.free_inputs():
            if a.kind == "scalar":
                out[a.name] = np.float32(1.0)
            else:
                out[a.name] = rng.standard_normal(workload.dims
                                                  ).astype(np.float32)
        return out

    # -- output buffers / direct slot writes ----------------------------------
    def _axis_epu(self, sct: SCT, part: ConcretePartitioning,
                  name: str) -> Optional[Tuple[int, int]]:
        """(partition_dim, epu) of a partitionable output, else None."""
        vp = part.plan.vectors.get(name)
        if vp is not None:
            return None if vp.copy else (vp.partition_dim, vp.epu)
        spec = output_spec(sct, name)
        if spec is not None and spec.partitionable:
            return (spec.partition_dim, spec.epu)
        return None

    def _get_buffer(self, name: str, shape: Tuple[int, ...],
                    dtype: np.dtype, leases: List[np.ndarray]) -> np.ndarray:
        """Lease a reusable output buffer to the calling run.

        A buffer leased to a still-running concurrent call is never
        handed out again; the requester gets a fresh allocation instead
        (stored as the new cached buffer).  Leases are released at the
        end of ``_execute`` — preserving the sequential aliasing
        contract (the next run may overwrite returned arrays) while
        overlapping runs stay isolated."""
        key = (name, tuple(shape), np.dtype(dtype).str)
        with self._buf_lock:
            buf = self._buffers.get(key)
            if buf is not None and id(buf) in self._inuse:
                buf = None              # leased to a concurrent run
            if buf is None:
                buf = np.empty(shape, dtype)
                if self.reuse_buffers:
                    self._buffers[key] = buf
            if self.reuse_buffers:
                self._inuse.add(id(buf))
                leases.append(buf)
        return buf

    def _output_targets(self, sct: SCT, part: ConcretePartitioning,
                        leases: List[np.ndarray]
                        ) -> Dict[str, _OutputTarget]:
        """Preallocated destinations for outputs whose shape is known.

        Shapes are learned from the first run of each (SCT, output); from
        then on slots write their partition directly into the shared
        buffer and the merge phase copies zero bytes."""
        targets: Dict[str, _OutputTarget] = {}
        sid = sct.unique_id()
        for name in _produced_names(sct):
            if name in self.merges:
                continue        # user merge fn takes precedence: no buffer
            ae = self._axis_epu(sct, part, name)
            if ae is None:
                continue
            axis, epu = ae
            known = self._out_shapes.get((sid, name))
            if known is None:
                continue
            shape, dtype = known
            if axis >= len(shape) or \
                    shape[axis] != part.plan.domain_units * epu:
                continue        # workload changed: re-learn on this run
            targets[name] = _OutputTarget(
                buffer=self._get_buffer(name, shape, dtype, leases),
                axis=axis, epu=epu)
        return targets

    def _direct_write(self, out_env: Dict[str, Any], seg: _Segment,
                      targets: Dict[str, _OutputTarget]) -> frozenset:
        """Write this segment's partitionable outputs straight into the
        preallocated buffers (zero-copy merge); returns the names written.
        An accelerator output arrives as its started :class:`_ReadBack`."""
        if not targets:
            return frozenset()
        written = set()
        for name, tg in targets.items():
            v = out_env.get(name)
            if v is None or getattr(v, "ndim", 0) < 1:
                continue
            expect = seg.units * tg.epu
            if np.shape(v)[tg.axis] != expect:
                continue        # kernel reshaped the output: merge-path copy
            idx = [slice(None)] * tg.buffer.ndim
            off = seg.start * tg.epu
            idx[tg.axis] = slice(off, off + expect)
            dst = tg.buffer[tuple(idx)]
            if np.shape(v) != dst.shape:
                continue
            if isinstance(v, _ReadBack):
                v.write(dst)
            else:
                dst[...] = v    # single conversion + copy
            written.add(name)
        return frozenset(written)

    # -- merging ---------------------------------------------------------------
    def _merge(self, sct: SCT, part: ConcretePartitioning,
               done: Sequence[Tuple[_Segment, _SlotResult]],
               targets: Optional[Dict[str, _OutputTarget]] = None,
               leases: Optional[List[np.ndarray]] = None
               ) -> Tuple[Dict[str, Any], int, int, int, int]:
        """Merge per-segment outputs; returns (outputs, bytes copied,
        bytes direct-written, bytes of accelerator-class outputs the
        copies read into host memory, device→host copies started).

        Precedence per output name (documented contract):
          1. a user-supplied merge function (``self.merges``) — honoured
             even when the output is also partitionable;
          2. in-place assembly along the partition dim (or, with
             ``inplace_merge=False``, the historical ``np.concatenate``)
             for partitionable array outputs;
          3. the first slot's value (COPY / replicated / scalar outputs).
        """
        targets = targets or {}
        leases = leases if leases is not None else []
        merged: Dict[str, Any] = {}
        bytes_copied = 0
        direct_bytes = 0
        read_back = 0
        blocks = 0
        accel = {j for j, s in enumerate(part.slots)
                 if s.device_type != "cpu"}
        sid = sct.unique_id()
        for name in _produced_names(sct):
            pieces = [(seg, res) for seg, res in done if name in res.outputs]
            if not pieces:
                continue
            parts = [res.outputs[name] for _, res in pieces]
            if name in self.merges:
                merged[name] = self.merges[name](parts)
                continue
            ae = self._axis_epu(sct, part, name)
            if ae is None or not all(getattr(p, "ndim", 0) >= 1
                                     for p in parts):
                merged[name] = parts[0]
                continue
            axis, _ = ae
            read_back += _device_bytes(
                p for (seg, res), p in zip(pieces, parts)
                if seg.slot in accel and name not in res.written)
            if not self.inplace_merge:
                merged[name] = np.concatenate(
                    [p if isinstance(p, np.ndarray) else np.asarray(p)
                     for p in parts], axis=axis)
                bytes_copied += merged[name].nbytes
                continue
            out, copied, direct, started = self._assemble(
                name, axis, pieces, targets.get(name), leases, accel)
            merged[name] = out
            bytes_copied += copied
            direct_bytes += direct
            blocks += started
            self._out_shapes[(sid, name)] = (tuple(out.shape), out.dtype)
        return merged, bytes_copied, direct_bytes, read_back, blocks

    def _assemble(self, name: str, axis: int,
                  pieces: Sequence[Tuple[_Segment, _SlotResult]],
                  target: Optional[_OutputTarget],
                  leases: List[np.ndarray], accel: set
                  ) -> Tuple[np.ndarray, int, int, int]:
        """In-place assembly of one partitionable output.

        Returns (array, bytes copied here, bytes already direct-written,
        device→host copies started).  Segments that wrote into the target
        buffer during compute are skipped; anything else is packed with
        one copy per part (no concat temporary), the parts of accelerator
        slots (``accel``, slot indices) through a :class:`_ReadBack`."""
        parts = [res.outputs[name] for _, res in pieces]
        sizes = [int(np.shape(p)[axis]) for p in parts]
        if target is not None:
            expected = all(
                s == seg.units * target.epu
                for s, (seg, _) in zip(sizes, pieces))
            if expected and target.buffer.shape[axis] == sum(sizes):
                copied = direct = 0
                fills = []
                for (seg, res), p, s in zip(pieces, parts, sizes):
                    off = seg.start * target.epu
                    idx = [slice(None)] * target.buffer.ndim
                    idx[axis] = slice(off, off + s)
                    n = s * int(np.prod(target.buffer.shape)
                                // max(target.buffer.shape[axis], 1)
                                ) * target.buffer.itemsize
                    if name in res.written:
                        direct += n
                        continue
                    fills.append((seg.slot in accel, p,
                                  target.buffer[tuple(idx)]))
                    copied += n
                return target.buffer, copied, direct, _fill(fills)
        # no (usable) target: learn the shape, pack into a reusable buffer
        first = parts[0]
        shape = list(np.shape(first))
        shape[axis] = sum(sizes)
        dtype = np.result_type(*[getattr(p, "dtype", None)
                                 or np.asarray(p).dtype for p in parts])
        buf = self._get_buffer(name, tuple(shape), dtype, leases)
        off = 0
        copied = 0
        fills = []
        for (seg, _), p, s in zip(pieces, parts, sizes):
            idx = [slice(None)] * buf.ndim
            idx[axis] = slice(off, off + s)
            fills.append((seg.slot in accel, p, buf[tuple(idx)]))
            copied += fills[-1][2].nbytes
            off += s
        return buf, copied, 0, _fill(fills)

    # -- residency -------------------------------------------------------------
    def _make_resident(self, sct: SCT, part: ConcretePartitioning,
                       done: Sequence[Tuple[_Segment, _SlotResult]],
                       prev: Optional[ResidentPartition],
                       inherited_extras: Dict[str, Any]) -> ResidentPartition:
        """Package a clean run's slot-local outputs as a resident handle.

        Vectors produced by *earlier* chain steps but not re-produced here
        are carried forward — slot-locally when ``prev`` is compatible
        (the layouts are identical by construction), as whole arrays via
        ``extras`` otherwise — so any later step can still consume them.
        """
        produced = _produced_names(sct)
        meta: Dict[str, Tuple[int, int]] = {}
        extras: Dict[str, Any] = {
            k: v for k, v in inherited_extras.items() if k not in produced}
        for name in produced:
            if name in self.merges:
                parts = [res.outputs[name] for _, res in done
                         if name in res.outputs]
                if parts:
                    extras[name] = self.merges[name](parts)
                continue
            ae = self._axis_epu(sct, part, name)
            if ae is not None and all(
                    getattr(res.outputs.get(name), "ndim", 0) >= 1
                    for _, res in done if name in res.outputs):
                meta[name] = ae
            else:
                parts = [res.outputs[name] for _, res in done
                         if name in res.outputs]
                if parts:
                    extras[name] = parts[0]
        envs: List[Dict[str, Any]] = []
        for i, (seg, res) in enumerate(done):
            env = {n: res.outputs[n] for n in meta if n in res.outputs}
            if prev is not None:
                for n, ae in prev.meta.items():
                    if n in produced or n in env:
                        continue
                    carried = prev.envs[i].get(n) if i < len(prev.envs) \
                        else None
                    if carried is not None:
                        env[n] = carried
                        meta.setdefault(n, ae)
            envs.append(env)
        layout = tuple((seg.start, seg.units) for seg, _ in done)
        return ResidentPartition(part=part, layout=layout, envs=envs,
                                 meta=meta, extras=extras,
                                 executor=self, sct=sct)


def _placement(out_env: Dict[str, Any], names: Sequence[str]) -> List[str]:
    """Sorted devices holding a slot's produced JAX outputs (host numpy
    outputs contribute ``"host"``)."""
    where = set()
    for name in names:
        v = out_env.get(name)
        if isinstance(v, jax.Array):
            where.update(str(d) for d in v.devices())
        elif v is not None:
            where.add("host")
    return sorted(where)


def _host_bytes(values: Iterable[Any]) -> int:
    """Bytes of the host-resident values (NumPy arrays and scalars)."""
    return sum(v.nbytes for v in values
               if isinstance(v, (np.ndarray, np.generic)))


def _device_bytes(values: Iterable[Any]) -> int:
    """Bytes of the ``jax.Array`` values."""
    return sum(v.nbytes for v in values if isinstance(v, jax.Array))


def _row_bounds(rows: int, row_bytes: int, align: int
                ) -> Tuple[Tuple[int, int], ...]:
    """Row ranges in which an output of ``rows`` rows of ``row_bytes``
    each is read back: whole when it fits in one ``D2H_BLOCK_BYTES``
    block, else blocks of a multiple of ``align`` rows, the last taking
    the remainder."""
    step = max(align, D2H_BLOCK_BYTES // max(row_bytes, 1) // align * align)
    if rows <= step:
        return ((0, rows),)
    return tuple((a, min(a + step, rows)) for a in range(0, rows, step))


@functools.lru_cache(maxsize=64)
def _cutter(bounds: Tuple[Tuple[int, int], ...]):
    """One device program that cuts an array into the row ``bounds``."""
    return jax.jit(lambda v: tuple(v[a:b] for a, b in bounds))


def _readable(v: Any) -> bool:
    """Whether ``v`` is an output the read-back takes (a ``jax.Array``
    with rows)."""
    return isinstance(v, jax.Array) and v.ndim >= 1


class _ReadBack:
    """The device→host copy of one accelerator output, started at once:
    whole, or, when the output is larger than ``D2H_BLOCK_BYTES``, cut on
    its device into row blocks of whole (8, 128) tiles (a 1-D output into
    runs of 8 × 128 elements), every block's copy in flight together.
    ``shape`` and ``ndim`` are the output's, so it stands in for the
    output where :meth:`ThreadedExecutor._direct_write` writes it."""

    def __init__(self, value: jax.Array):
        self.shape, self.ndim = value.shape, value.ndim
        rows = value.shape[0]
        bounds = _row_bounds(rows, value.nbytes // max(rows, 1),
                             _TILE_ROWS if value.ndim > 1 else _TILE_ELEMS)
        pieces = _cutter(bounds)(value) if len(bounds) > 1 else (value,)
        for p in pieces:
            p.copy_to_host_async()
        self.blocks = list(zip(pieces, bounds))

    def write(self, dst: np.ndarray) -> None:
        """Each block into its rows of ``dst``, in order, as its copy lands
        (the later ones still in flight).  ``np.asarray`` first: numpy,
        handed a TPU ``jax.Array`` itself, asks for its buffer, is refused,
        and takes three times as long (PERF.md, Findings PR 14)."""
        for p, (a, b) in self.blocks:
            dst[a:b] = np.asarray(p)


def _fill(fills: Sequence[Tuple[bool, Any, np.ndarray]]) -> int:
    """Copy ``(from an accelerator slot, value, destination)`` parts into
    host buffers, the accelerator's through a :class:`_ReadBack` (all
    started before the host's are copied); returns the device→host
    copies."""
    reads = [(_ReadBack(v), d) for a, v, d in fills if a and _readable(v)]
    for a, v, d in fills:
        if not (a and _readable(v)):
            d[...] = v
    for r, d in reads:
        r.write(d)
    return sum(len(r.blocks) for r, _ in reads)


def _produced_names(sct: SCT) -> List[str]:
    names: List[str] = []
    for leaf in sct.leaves():
        for a in leaf.spec.outputs:
            if a.name not in names:
                names.append(a.name)
    # include function-reduction outputs of MapReduce nodes
    from repro.core.skeletons import MapReduce
    stack = [sct]
    while stack:
        n = stack.pop()
        if isinstance(n, MapReduce) and n.host_side_reduction:
            src = n.map_stage.output_names()
            if len(src) == 1:
                dst = n.out_name or f"{src[0]}_reduced"
                if dst not in names:
                    names.append(dst)
        stack.extend(n.children())
    return names


class Future:
    """Marrow's asynchronous execution handle (paper Table 1).

    ``get`` re-raises executor failures as
    :class:`~repro.core.faults.ExecutionError` with the failing slot /
    device identity attached, instead of a bare pool exception.
    """

    def __init__(self, inner: cf.Future, deadline: Optional[float] = None):
        self._inner = inner
        self._deadline = deadline

    def get(self, timeout: Optional[float] = None):
        timeout = timeout if timeout is not None else self._deadline
        try:
            return self._inner.result(timeout)
        except ExecutionError:
            raise
        except cf.TimeoutError:
            raise ExecutionError(
                f"request did not complete within {timeout}s") from None
        except Exception as e:
            raise ExecutionError(
                f"execution failed: {type(e).__name__}: {e}",
                getattr(e, "records", [])) from e

    def done(self) -> bool:
        return self._inner.done()


class _HandleFuture:
    """``concurrent.futures``-shaped view of one :class:`GraphHandle`
    node (duck-typed inner future for :class:`Future`)."""

    def __init__(self, handle: GraphHandle, extract: Callable[..., Any]):
        self._handle = handle
        self._extract = extract

    def result(self, timeout: Optional[float] = None):
        self._handle.result(timeout)    # raises on failure / wait timeout
        return self._extract(self._handle)

    def done(self) -> bool:
        return self._handle.done()


class Session:
    """User-facing facade: SCT.run()/submit() -> Future over a Scheduler.

    Usable as a context manager (``with Session(sched) as s: ...`` shuts
    the request queue down on exit).  Requests are admitted concurrently
    — :meth:`submit` takes a whole :class:`~repro.core.graph.JobGraph`
    and returns a :class:`~repro.core.graph.GraphHandle`; ``run`` and
    ``run_chain`` are thin wrappers over one-node / linear graphs and
    keep their historical signatures and ``Future`` semantics.  At most
    ``max_inflight`` graphs may be unsettled at once; beyond that,
    ``submit`` blocks (backpressure) until one completes.

    Recurrent submissions are transparent to callers but cheaper: a
    structurally identical graph over same-shaped arrays is served from
    the scheduler's whole-graph plan cache (every node pre-planned, no
    decide/plan lock traffic), and — when the scheduler was built with
    ``fusion_window > 0`` — identical single-node graphs submitted
    within the window coalesce into one wider run whose merged output
    is sliced back per request.  Both paths settle the returned
    ``GraphHandle``/``Future`` exactly as the ordinary path does, with
    bit-identical outputs.

    ``run`` accepts a request-level ``deadline`` (seconds, enforced
    across retries and by ``Future.get``) and ``retries`` with
    exponential backoff on terminal
    :class:`~repro.core.faults.ExecutionError`; each backoff pause is
    capped by the remaining deadline.  ``shutdown`` drains in-flight
    requests, then closes the scheduler's graph pool and executor
    (persistent work queues, reusable output buffers — see
    :class:`ThreadedExecutor`); it is idempotent.

    ``telemetry`` installs a shared :class:`~repro.core.telemetry.Telemetry`
    bundle across the scheduler, executor, health tracker and balancer;
    :meth:`metrics`, :meth:`counters`, :meth:`export_trace` and
    :meth:`prometheus` expose what it collected.  Without one, the
    pipeline runs on the no-op ``NULL_TELEMETRY`` (off-by-default cheap).
    """

    def __init__(self, scheduler, *,
                 telemetry: Optional[Telemetry] = None,
                 max_inflight: int = 8):
        self.scheduler = scheduler
        if telemetry is not None and hasattr(scheduler, "attach_telemetry"):
            scheduler.attach_telemetry(telemetry)
        self.telemetry = getattr(scheduler, "telemetry", None) \
            or telemetry or NULL_TELEMETRY
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self.max_inflight = max_inflight
        self._inflight = threading.BoundedSemaphore(max_inflight)
        self._closed = False

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown()

    # -- graph pipeline -------------------------------------------------------
    def submit(self, graph: JobGraph, *, deadline: Optional[float] = None,
               retries: int = 0, retry_backoff: float = 0.05,
               **arrays) -> GraphHandle:
        """Submit a JobGraph for concurrent execution; returns its handle.

        Blocks while ``max_inflight`` earlier submissions are still
        unsettled (backpressure); per-node ``retries`` / ``deadline``
        semantics match :meth:`run`.  A ``submit`` span covers the wait
        and the scheduler's admission, and notes the request id."""
        if self._closed:
            raise RuntimeError("session is shut down")
        with self.telemetry.tracer.span("submit") as sp:
            self._inflight.acquire()
            try:
                handle = self.scheduler.submit(
                    graph, arrays, deadline=deadline, retries=retries,
                    retry_backoff=retry_backoff)
            except BaseException:
                self._inflight.release()
                raise
            sp.note(request=handle.request_id)
        handle.add_done_callback(lambda _h: self._inflight.release())
        return handle

    def gather(self, *handles: GraphHandle,
               timeout: Optional[float] = None) -> List[GraphResult]:
        """Block for a set of submitted graphs; returns their results in
        argument order (raising the first failure encountered)."""
        return [h.result(timeout) for h in handles]

    def run(self, sct: SCT, *, deadline: Optional[float] = None,
            retries: int = 0, retry_backoff: float = 0.05,
            **arrays) -> Future:
        graph = JobGraph()
        name = graph.add(sct)
        handle = self.submit(graph, deadline=deadline, retries=retries,
                             retry_backoff=retry_backoff, **arrays)
        return Future(_HandleFuture(handle, lambda h: h.runs[name]),
                      deadline=deadline)

    def run_chain(self, scts: Sequence[SCT], *, deadline: Optional[float] = None,
                  retries: int = 0, **arrays) -> Future:
        """Asynchronously run a compound SCT chain with partitioned
        residency between steps (a linear ``JobGraph``: residency flows
        along its chain edges exactly as in ``Scheduler.run_chain``)."""
        graph = JobGraph()
        names = graph.add_chain(list(scts))
        handle = self.submit(graph, deadline=deadline, retries=retries,
                             **arrays)
        return Future(
            _HandleFuture(handle, lambda h: [h.runs[n] for n in names]),
            deadline=deadline)

    # -- observability --------------------------------------------------------
    def metrics(self) -> Dict[str, Any]:
        """JSON snapshot of every metric series the pipeline recorded."""
        return self.telemetry.metrics.snapshot()

    def prometheus(self) -> str:
        """Prometheus text-format dump of the metrics registry."""
        return self.telemetry.metrics.to_prometheus()

    def counters(self) -> Dict[str, float]:
        """Namespaced pipeline counters (see ``Scheduler.counters``)."""
        counters = getattr(self.scheduler, "counters", None)
        return counters() if counters is not None else {}

    def events(self, kind: Optional[str] = None):
        """Recent structured events, optionally filtered by kind prefix."""
        return self.telemetry.events.records(kind)

    def export_trace(self, path: str) -> Dict[str, Any]:
        """Write the Chrome/Perfetto ``trace.json``; returns the object."""
        return self.telemetry.export_trace(path)

    def shutdown(self) -> None:
        """Drain in-flight graphs and release every execution resource.

        Idempotent — repeated calls (or a context-manager exit after an
        explicit shutdown) are no-ops."""
        if self._closed:
            return
        self._closed = True
        close = getattr(self.scheduler, "close", None)
        if close is not None:
            close()                     # drains, then closes the executor
            return
        exclose = getattr(getattr(self.scheduler, "executor", None),
                          "close", None)
        if exclose is not None:
            exclose()
