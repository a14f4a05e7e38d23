"""Compile the main path's Pallas kernels, the FFT SCT body and the filter
SCT's compiled program for a TPU v5e at the sizes ``chip_smoke.py`` and
the benchmark run, without a chip attached.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs these tests loads the TPU compiler.  Nothing
runs; a compile that the chip's compiler refuses fails here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.paper_suite import (FFT_ELEMS, SEG_PLANE, fft_sct,
                                    filter_pipeline_sct)
from chip_smoke import KERNEL_SIZES, SIZES
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-device compile can be written to the persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_case(name):
    n = KERNEL_SIZES[name]
    return {
        "saxpy": (lambda a, x, y: ops.saxpy(a, x, y), [(), (n,), (n,)]),
        "filter_pipeline": (lambda img: ops.filter_pipeline(img, 0),
                            [(n, n)]),
        "segmentation": (ops.segmentation, [(n, *SEG_PLANE)]),
        "nbody": (ops.nbody_accelerations, [(n, 3), (n,)]),
    }[name]


@pytest.mark.parametrize("name", sorted(KERNEL_SIZES))
def test_paper_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_case(name)
    text = _compile_text(fn, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in text


def test_fft_sct_body_compiles_for_v5e(one_chip):
    sct = fft_sct()
    text = _compile_text(lambda sig: sct.apply({"sig": sig})["sig_out"],
                         (SIZES["fft"], FFT_ELEMS), sharding=one_chip)
    assert "fft" in text.lower()


def test_filter_sct_body_compiles_for_v5e_as_one_program(one_chip):
    """The chip slot's segment of ``filter.4096.closed`` (3277 of 4096
    rows at the default split) as the executor runs it: the whole
    pipeline one program, the first row a traced operand."""
    rows, width = 3277, 4096
    img = jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip)
    row0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered = filter_pipeline_sct(width).program().lower({"img": img}, row0,
                                                         rows)
    assert sorted(lowered.out_info) == ["noisy", "out", "sol"]
    text = lowered.compile().as_text()
    assert "rev" in text and "s32[]" in text


#: output shapes the accelerator slot cuts before its read-back: one filter
#: output (3277 of 4096 rows at the default split), and saxpy's ``z`` at
#: 10^7 elements (8 * 10^6 on the chip), a 1-D output cut in tile runs
READ_BACK_SHAPES = {"filter_rows": (3277, 4096), "saxpy_1d": (8_000_000,)}


@pytest.mark.parametrize("case", sorted(READ_BACK_SHAPES))
def test_read_back_row_cut_compiles_for_v5e(case, one_chip):
    from repro.core.executor import (_TILE_ELEMS, _TILE_ROWS, _cutter,
                                     _row_bounds)
    shape = READ_BACK_SHAPES[case]
    align = _TILE_ROWS if len(shape) > 1 else _TILE_ELEMS
    row_bytes = 4 * (shape[1] if len(shape) > 1 else 1)
    bounds = _row_bounds(shape[0], row_bytes, align)
    assert len(bounds) > 1
    assert all(a % align == 0 for a, _ in bounds)
    assert bounds[-1][1] == shape[0]
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _cutter(bounds).lower(x).compile().as_text()
    assert text.count("slice") >= len(bounds)
