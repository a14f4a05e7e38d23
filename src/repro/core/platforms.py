"""Execution platforms (paper Sec. 2.2) — the technology-bound lower layer.

The Marrow runtime delegates device specificities to *execution platforms*:

* ``CPUExecutionPlatform`` — OpenCL **device fission**: splits a multi-core
  CPU device into sub-devices along cache/NUMA affinity domains
  (L1 < L2 < L3 < NUMA < NO_FISSION) to leverage data locality.
* ``GPUExecutionPlatform`` — **multi-buffering / overlap**: N in-flight
  executions per GPU so communication overlaps computation, plus the
  occupancy-ordered work-group size candidates.

TPU adaptation:

* :class:`HostPlatform` keeps the paper's fission semantics over the host
  CPU; :meth:`HostPlatform.from_jax` binds it to JAX's CPU device, so host
  slots really run on the CPU backend.
* :class:`AcceleratorPlatform` is the fast class, one member per chip
  (:meth:`AcceleratorPlatform.from_jax`); overlap is the number of
  in-flight executions per chip (the paper's GPU multi-buffering).

Install-time calibration (paper: SHOC suite) is
:func:`AcceleratorPlatform.calibrate` — relative throughput scores that
drive the *static* intra-class distribution of Sec. 3.2.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

from repro.core.occupancy import BlockScore, candidates
from repro.core.spec import KernelSpec

#: Fission levels in the paper's search order (L1 first — most sub-devices,
#: most locality — down to NO_FISSION).
FISSION_LEVELS = ("L1", "L2", "L3", "NUMA", "NO_FISSION")


@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    """One schedulable device (or device class member).

    ``jax_device`` binds the device to a real ``jax.Device``: slots built
    from it run their kernels there.  Hand-built entries (tests, the
    simulator) leave it ``None`` and run on JAX's default device; their
    peaks are the analytic defaults below.  Entries built by
    :meth:`AcceleratorPlatform.from_jax` / :meth:`HostPlatform.from_jax`
    keep the device, whose ``platform`` and ``device_kind`` say what it
    is, and leave the peaks unknown (``None``) rather than guessing them.
    """

    name: str
    kind: str                  # scheduling class: "cpu" host, else accelerator
    compute_units: int = 1     # cores / chips in the device
    peak_flops: Optional[float] = 197e12   # analytic default (v5e bf16)
    hbm_bw: Optional[float] = 819e9
    link_bw: Optional[float] = 50e9
    throughput: float = 1.0    # calibrated relative score (SHOC analogue)
    jax_device: Any = dataclasses.field(default=None, compare=False,
                                        repr=False)


def _from_jax_device(name: str, kind: str, dev: Any,
                     compute_units: int = 1) -> DeviceInfo:
    return DeviceInfo(name, kind, compute_units=compute_units,
                      peak_flops=None, hbm_bw=None, link_bw=None,
                      jax_device=dev)


@dataclasses.dataclass(frozen=True)
class FissionConfig:
    level: str
    subdevices: int            # execution slots the level yields


class HostPlatform:
    """CPU/slow-class platform: fission by affinity domain.

    ``topology`` maps each supported fission level to the number of
    sub-devices it yields (paper Sec. 4.1 example: 64-core 4-socket Opteron
    -> L1:64? the paper's table uses L2:32, L3:8, NUMA:4).  Levels absent
    from the map are unsupported by the hardware.
    """

    def __init__(self, device: DeviceInfo,
                 topology: Optional[Dict[str, int]] = None):
        self.device = device
        cu = device.compute_units
        self.topology: Dict[str, int] = topology or {
            "L1": cu, "L2": max(cu // 2, 1), "L3": max(cu // 8, 1),
            "NUMA": max(cu // 16, 1), "NO_FISSION": 1,
        }
        self._level = "NO_FISSION"

    @classmethod
    def from_jax(cls) -> "HostPlatform":
        """The host class bound to JAX's first CPU device, with one compute
        unit per host core."""
        import jax
        dev = jax.devices("cpu")[0]
        return cls(_from_jax_device("host", "cpu", dev, os.cpu_count() or 1))

    # paper: CPUExecutionPlatform.getConfigurations(SCT, args)
    def get_configurations(self, sct=None, arguments=None) -> List[FissionConfig]:
        return [FissionConfig(lv, self.topology[lv]) for lv in FISSION_LEVELS
                if lv in self.topology]

    def configure(self, level: str) -> int:
        """Apply a fission level; returns the parallelism it contributes."""
        if level not in self.topology:
            raise ValueError(f"unsupported fission level {level}")
        self._level = level
        return self.topology[level]

    @property
    def level(self) -> str:
        return self._level

    @property
    def parallelism(self) -> int:
        return self.topology[self._level]


class AcceleratorPlatform:
    """GPU/fast-class platform: overlap depth + block-size candidates."""

    def __init__(self, devices: Sequence[DeviceInfo], *, max_overlap: int = 8,
                 occupancy_threshold: float = 0.80):
        if not devices:
            raise ValueError("AcceleratorPlatform needs >= 1 device")
        self.devices = list(devices)
        self.max_overlap = max_overlap
        self.occupancy_threshold = occupancy_threshold
        self._overlap = 1

    @classmethod
    def from_jax(cls, devices: Optional[Sequence[Any]] = None
                 ) -> "AcceleratorPlatform":
        """One class member per ``jax.Device`` (default: ``jax.devices()``),
        named ``<platform><id>`` and classed by its platform."""
        import jax
        devs = list(jax.devices() if devices is None else devices)
        return cls([_from_jax_device(f"{d.platform}{d.id}", d.platform, d)
                    for d in devs])

    # paper: GPUExecutionPlatform.getConfigurations -> ({overlaps}, {wgs})
    def get_configurations(self, sct=None, arguments=None,
                           domain_size: int = 1 << 20
                           ) -> Tuple[List[int], Dict[str, List[BlockScore]]]:
        overlaps = list(range(1, self.max_overlap + 1))
        wgs: Dict[str, List[BlockScore]] = {}
        specs: Iterable[KernelSpec] = (sct.kernel_specs() if sct is not None
                                       else [])
        for spec in specs:
            wgs[spec.name] = candidates(
                spec, domain_size,
                cores=sum(d.compute_units for d in self.devices),
                threshold=self.occupancy_threshold)
        return overlaps, wgs

    def configure(self, overlap: int) -> int:
        """Set the overlap factor; returns contributed parallelism
        (paper: #GPUs x overlap concurrent executions)."""
        if not 1 <= overlap <= self.max_overlap:
            raise ValueError(f"overlap {overlap} out of range")
        self._overlap = overlap
        return len(self.devices) * overlap

    @property
    def overlap(self) -> int:
        return self._overlap

    @property
    def parallelism(self) -> int:
        return len(self.devices) * self._overlap

    # -- install-time calibration (SHOC analogue) ---------------------------
    def calibrate(self, workload: Optional[Callable[[DeviceInfo], float]] = None
                  ) -> List[float]:
        """Relative throughput per device, for the static intra-class split.

        Without a ``workload`` timer the scores are analytic: peak_flops
        times throughput, or throughput alone when any member's peak is
        unknown (devices built by :meth:`from_jax`) — an even split for
        identical devices.  When a ``workload`` timer is supplied (real
        hardware), scores are the inverse measured times.
        """
        if workload is None:
            if any(d.peak_flops is None for d in self.devices):
                scores = [d.throughput for d in self.devices]
            else:
                scores = [d.peak_flops * d.throughput for d in self.devices]
        else:
            times = [max(workload(d), 1e-12) for d in self.devices]
            scores = [1.0 / t for t in times]
        tot = sum(scores)
        return [s / tot for s in scores]


def timed(fn: Callable[[], None], *, repeats: int = 3) -> float:
    """Best-of-N wall-clock timer used by calibration and the autotuner."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best
