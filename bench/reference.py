"""The benchmark's yardstick for results and work: plain references, the
comparison that decides ``correct``, its lower-precision control, and the
least bytes and operations each SCT's work needs.

``make_inputs``, ``reference``, ``TOLERANCE`` and ``max_error`` are copies of
``benchmarks/paper_suite.py`` as of the commit that added this file, kept
here so that a change to the program cannot change what it is judged by.
``bench/tests/test_bench_reference.py`` holds the copies equal to the
originals at small sizes.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

FFT_ELEMS = 512 * 1024 // 8        # one 512 KiB FFT (f64 complex pairs)
SEG_PLANE = (1024, 1024)


def make_inputs(name: str, size: int, seed=0) -> Dict[str, np.ndarray]:
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


def checked_error(got: Dict[str, object], want: Dict[str, np.ndarray]
                  ) -> float:
    """``max_error``, except that an output holding a NaN or an infinity
    reads as infinitely wrong (``max_error`` alone lets a NaN through: it
    compares as no larger than any error)."""
    for name in want:
        if not np.all(np.isfinite(np.asarray(got[name], np.float32))):
            return float("inf")
    return max_error(got, want)


# ---------------------------------------------------------------------------
# Control: the reference one precision step down (bfloat16 for float32)
# ---------------------------------------------------------------------------

def control(name: str, inputs: Dict[str, np.ndarray]
            ) -> Dict[str, np.ndarray]:
    """The reference computed in bfloat16 with ``jax.numpy`` on JAX's
    default device, returned as float32 NumPy.  It stands where the
    program stands; a limit that lets it pass is too loose."""
    import jax.numpy as jnp
    bf16 = jnp.bfloat16
    if name == "filter_pipeline":
        img = jnp.asarray(inputs["img"]).astype(bf16)
        rows, cols = jnp.arange(img.shape[0]), jnp.arange(img.shape[1])
        h = (rows[:, None] * 31 + cols[None, :] * 17) % 13
        noisy = jnp.clip(img + (h.astype(bf16) - bf16(6)), 0, 255)
        sol = jnp.where(noisy > 128, bf16(255) - noisy, noisy)
        outs = {"noisy": noisy, "sol": sol, "out": sol[:, ::-1]}
    elif name == "saxpy":
        x = jnp.asarray(inputs["x"]).astype(bf16)
        y = jnp.asarray(inputs["y"]).astype(bf16)
        outs = {"z": bf16(inputs["a"]) * x + y}
    else:
        raise KeyError(f"no control for {name!r}")
    return {k: np.asarray(v.astype(jnp.float32)) for k, v in outs.items()}


# ---------------------------------------------------------------------------
# Least work per domain unit, from shapes alone
# ---------------------------------------------------------------------------

def unit_work(name: str, size: int) -> Tuple[float, float]:
    """(operations, bytes) that one domain unit of the SCT needs at least,
    whatever implements it.

    Both SCTs here are elementwise maps, so bytes bound them on any chip:
    their operations per byte (under 0.5) lie far below a TPU's ridge
    point (about 240 for the v5e).

    * ``filter_pipeline``: one unit is an image line of ``size`` pixels.
      Each pixel is read once (4 B) and its three outputs ``noisy``,
      ``sol`` and ``out`` written once (12 B); the float work is the
      noise's add, subtract and two-sided clip and the solarize's compare,
      subtract and select: 7 operations.  The integer hash of the noise
      is not counted.
    * ``saxpy``: one unit is one element: ``x`` and ``y`` read (8 B), ``z``
      written (4 B); one multiply and one add.
    """
    if name == "filter_pipeline":
        return 7.0 * size, 16.0 * size
    if name == "saxpy":
        return 2.0, 12.0
    raise KeyError(f"no work model for {name!r}")
