"""The paper's five benchmarks as Marrow SCTs + calibrated testbeds.

Benchmarks (paper Sec. 4): Filter Pipeline (Pipeline), FFT (Pipeline),
N-Body (Loop, COPY dataset), Saxpy (Map), Segmentation (Map, 3-D).
``flops/bytes_per_item`` calibrate the simulator's cost model; the
elementary partitioning units mirror the paper exactly (image line, one
FFT, one body, one element, one plane).

Testbeds:
  * OPTERON — Sec. 4.1: 4x 16-core AMD Opteron 6272 (CPU-only),
    16 KiB L1 / 2 MiB L2 per 2 cores / 6 MiB L3 per 8 cores.
  * HYBRID  — Sec. 4.2: i7-3930K (6C12T) + 1-2x AMD HD 7950 on PCIe x16.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.core import (KernelSpec, Loop, LoopState, Map, MapReduce,
                        Pipeline, SCT, kernel, scalar, vector)
from repro.core.simulator import CostModel, SimDevice
from repro.core.spec import Trait, Workload


# ---------------------------------------------------------------------------
# SCT builders (jnp bodies are real; the simulator uses only the specs)
# ---------------------------------------------------------------------------

def filter_pipeline_sct(width: int = 1024) -> SCT:
    """Gaussian Noise -> Solarize -> Mirror; epu = image line, nu = 2.

    The noise hashes each pixel's global (row, col): a partition's first
    row arrives through the OFFSET trait, so any split of the image gives
    the whole-image result."""
    import jax.numpy as jnp

    def noise(img, row0):
        rows = row0 + jnp.arange(img.shape[0])
        h = (rows[:, None] * 31 + jnp.arange(img.shape[1])[None, :] * 17) % 13
        return jnp.clip(img + (h.astype(img.dtype) - 6.0), 0, 255)

    k1 = kernel(noise, name="gauss_noise",
                inputs=[vector("img", epu=1),
                        scalar("row0", trait=Trait.OFFSET)],
                outputs=[vector("noisy", epu=1)],  # 2 px/thread is intra-line
                flops_per_item=6 * width, bytes_per_item=8 * width)
    k2 = kernel(lambda x: np_where_solarize(x), name="solarize",
                inputs=[vector("noisy", epu=1)],
                outputs=[vector("sol", epu=1)],
                flops_per_item=2 * width, bytes_per_item=8 * width)
    k3 = kernel(lambda x: x[:, ::-1], name="mirror",
                inputs=[vector("sol", epu=1)],
                outputs=[vector("out", epu=1)],
                flops_per_item=1 * width, bytes_per_item=8 * width)
    return Pipeline(k1, k2, k3)


def np_where_solarize(x):
    import jax.numpy as jnp
    return jnp.where(x > 128.0, 255.0 - x, x)


FFT_ELEMS = 512 * 1024 // 8        # one 512 KiB FFT (f64 complex pairs)


def fft_sct() -> SCT:
    """FFT -> iFFT pipeline; epu = one whole FFT (paper: 512 KiB)."""
    import jax.numpy as jnp
    lg = math.log2(FFT_ELEMS)
    k1 = kernel(lambda x: jnp.real(jnp.fft.fft(x, axis=1)).astype(x.dtype),
                name="fft", inputs=[vector("sig", epu=1)],
                outputs=[vector("freq", epu=1)],
                flops_per_item=5 * FFT_ELEMS * lg,
                bytes_per_item=16 * FFT_ELEMS)
    k2 = kernel(lambda x: jnp.real(jnp.fft.ifft(x, axis=1)).astype(x.dtype),
                name="ifft", inputs=[vector("freq", epu=1)],
                outputs=[vector("sig_out", epu=1)],
                flops_per_item=5 * FFT_ELEMS * lg,
                bytes_per_item=16 * FFT_ELEMS)
    return Pipeline(k1, k2)


def nbody_sct(n_bodies: int, iterations: int = 1) -> SCT:
    """Direct-sum N-Body; COPY dataset, partitioned at body level."""
    import jax.numpy as jnp

    def step(mine, all_pos):
        d = all_pos[None, :, :3] - mine[:, None, :3]
        r2 = (d * d).sum(-1) + 1e-3
        acc = (d / (r2 ** 1.5)[..., None]).sum(1)
        return jnp.asarray(mine).at[:, :3].add(0.001 * acc)

    body = kernel(step, name="nbody_step",
                  inputs=[vector("bodies", epu=1),
                          vector("all_bodies", copy=True)],
                  outputs=[vector("bodies", epu=1)],
                  flops_per_item=20.0 * n_bodies,
                  bytes_per_item=16.0)
    return Loop(body, LoopState(max_iterations=iterations,
                                global_sync=True))


def saxpy_sct() -> SCT:
    import jax.numpy as jnp
    k = kernel(lambda a, x, y: jnp.multiply(a, x) + y, name="saxpy",
               inputs=[scalar("a"), vector("x", epu=1),
                       vector("y", epu=1)],
               outputs=[vector("z", epu=1)],
               flops_per_item=2.0, bytes_per_item=12.0)
    return Map(k)


def segmentation_sct(plane: int = 1024 * 1024) -> SCT:
    """3-D gray volume -> 3 classes; epu = one (D1 x D2) plane."""
    import jax.numpy as jnp
    k = kernel(lambda v: jnp.where(v < 85, 0.0,
                                   jnp.where(v > 170, 255.0, 128.0)),
               name="segmentation",
               inputs=[vector("vol", epu=1)],
               outputs=[vector("seg", epu=1)],
               flops_per_item=2.0 * plane, bytes_per_item=8.0 * plane)
    return Map(k)


#: name -> (sct builder(size), workload sizes, workload label) — the
#: paper's parameterisation classes (Table 2 / Table 3)
BENCHMARKS: Dict[str, Tuple] = {
    "filter_pipeline": (lambda n: filter_pipeline_sct(n),
                        [1024, 2048, 4096, 8192], "image size (px)"),
    "fft": (lambda n: fft_sct(),
            [256, 512, 1024], "#FFTs (512KiB each)"),
    "nbody": (lambda n: nbody_sct(n),
              [8192, 16384, 32768], "bodies"),
    "saxpy": (lambda n: saxpy_sct(),
              [10 ** 6, 10 ** 7, 5 * 10 ** 7], "elements"),
    "segmentation": (lambda n: segmentation_sct(),
                     [64, 512, 3840], "planes (1Mpx)"),
}


# ---------------------------------------------------------------------------
# Inputs and plain float32 references (NumPy, whole input, no scheduler)
# ---------------------------------------------------------------------------

SEG_PLANE = (1024, 1024)


def make_inputs(name: str, size: int, seed: int = 0) -> Dict[str, np.ndarray]:
    """Random float32 request arrays for one benchmark at ``size``."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    if name == "filter_pipeline":
        return {"img": rng.random((size, size), f32) * f32(255)}
    if name == "fft":
        return {"sig": rng.standard_normal((size, FFT_ELEMS), f32)}
    if name == "nbody":
        bodies = rng.standard_normal((size, 4), f32)
        return {"bodies": bodies, "all_bodies": bodies}
    if name == "saxpy":
        return {"a": f32(2.5), "x": rng.standard_normal(size, f32),
                "y": rng.standard_normal(size, f32)}
    if name == "segmentation":
        return {"vol": rng.random((size, *SEG_PLANE), f32) * f32(255)}
    raise KeyError(name)


def reference(name: str, inputs: Dict[str, np.ndarray]
              ) -> Dict[str, np.ndarray]:
    """Every output of the benchmark's SCT, computed in float32 NumPy."""
    f32 = np.float32
    if name == "filter_pipeline":
        img = inputs["img"]
        rows, cols = np.arange(img.shape[0]), np.arange(img.shape[1])
        h = (rows[:, None] * 31 + cols[None, :] * 17) % 13
        noisy = np.clip(img + (h.astype(f32) - f32(6)), 0, 255).astype(f32)
        sol = np.where(noisy > 128, f32(255) - noisy, noisy)
        return {"noisy": noisy, "sol": sol, "out": sol[:, ::-1]}
    if name == "fft":
        freq = np.real(np.fft.fft(inputs["sig"], axis=1)).astype(f32)
        return {"freq": freq,
                "sig_out": np.real(np.fft.ifft(freq, axis=1)).astype(f32)}
    if name == "nbody":
        mine, pos = inputs["bodies"], inputs["all_bodies"][:, :3]
        out = mine.copy()
        for i in range(0, len(mine), 512):      # bound the (i, j, 3) block
            d = pos[None, :, :] - mine[i:i + 512, None, :3]
            r2 = (d * d).sum(-1) + f32(1e-3)
            acc = (d / (r2 ** f32(1.5))[..., None]).sum(1)
            out[i:i + 512, :3] += f32(0.001) * acc
        return {"bodies": out}
    if name == "saxpy":
        return {"z": inputs["a"] * inputs["x"] + inputs["y"]}
    if name == "segmentation":
        v = inputs["vol"]
        return {"seg": np.where(v < 85, f32(0),
                                np.where(v > 170, f32(255), f32(128)))}
    raise KeyError(name)


#: largest |got - ref| / max(|ref|max, 1) each benchmark may show: exact
#: elementwise maps, summation order for the N-body sum and the FFTs
TOLERANCE: Dict[str, float] = {"filter_pipeline": 1e-6, "fft": 1e-4,
                               "nbody": 1e-4, "saxpy": 1e-6,
                               "segmentation": 1e-6}


def max_error(got: Dict[str, object], want: Dict[str, np.ndarray]) -> float:
    """Worst normalised error over every reference output."""
    worst = 0.0
    for name, ref in want.items():
        g = np.asarray(got[name], np.float32)
        if g.shape != ref.shape:
            return float("inf")
        scale = max(float(np.abs(ref).max(initial=0.0)), 1.0)
        worst = max(worst, float(np.abs(g - ref).max(initial=0.0)) / scale)
    return worst


# ---------------------------------------------------------------------------
# Calibrated testbeds (paper hardware)
# ---------------------------------------------------------------------------

def opteron_testbed() -> List[SimDevice]:
    """Sec. 4.1: 4x Opteron 6272, 64 cores total, ~2.2 GHz."""
    return [SimDevice("cpu", "cpu", flops=280e9, mem_bw=51e9,
                      pcie_bw=math.inf, cores=64)]


def hybrid_testbed(n_gpus: int = 1) -> List[SimDevice]:
    """Sec. 4.2: i7-3930K + n x AMD HD 7950 (PCIe x16)."""
    devs = [SimDevice(f"gpu{i}", "gpu", flops=2870e9, mem_bw=240e9,
                      pcie_bw=8e9, cores=28) for i in range(n_gpus)]
    devs.append(SimDevice("cpu", "cpu", flops=150e9, mem_bw=43e9,
                          pcie_bw=math.inf, cores=6))
    return devs


def workload_for(name: str, size: int) -> Workload:
    if name == "filter_pipeline":
        return Workload((size, size))
    if name == "fft":
        return Workload((size, FFT_ELEMS), itemsize=8)
    if name == "nbody":
        return Workload((size, 4))
    if name == "segmentation":
        return Workload((size, 1024, 1024))
    return Workload((size,))


def cost_model_for(name: str, size: int) -> CostModel:
    """Per-domain-unit analytic costs (drives the simulator)."""
    w = workload_for(name, size)
    if name == "filter_pipeline":
        per_line = size
        return CostModel(flops_per_unit=9.0 * per_line,
                         bytes_per_unit=24.0 * per_line)
    if name == "fft":
        lg = math.log2(FFT_ELEMS)
        return CostModel(flops_per_unit=10 * FFT_ELEMS * lg,
                         bytes_per_unit=32.0 * FFT_ELEMS)
    if name == "nbody":
        return CostModel(flops_per_unit=20.0 * size, bytes_per_unit=32.0,
                         iterations=1.0)
    if name == "segmentation":
        return CostModel(flops_per_unit=2.0 * (1 << 20),
                         bytes_per_unit=8.0 * (1 << 20))
    return CostModel(flops_per_unit=2.0, bytes_per_unit=12.0)
