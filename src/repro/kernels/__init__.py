"""Pallas TPU kernels for the compute hot spots (+ jnp oracles in ref.py).

Layout:
  flash_attention.py  pl.pallas_call online-softmax attention (GQA/SWA/cap)
  ssd_scan.py         Mamba2 SSD chunk recurrence (state in VMEM scratch)
  moe_gemm.py         grouped expert GEMM (MegaBlocks-style)
  saxpy.py, filter_pipeline.py, segmentation.py, nbody.py
                      the paper's own benchmark suite (Sec. 4)
  ops.py              wrappers; interpret=True only when the caller asks
  ref.py              pure-jnp oracles for allclose tests
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
