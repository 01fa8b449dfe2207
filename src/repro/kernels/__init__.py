"""Pallas TPU kernels for FlashSparse SpMM / SDDMM — single-head and
batched (H, ...) grids — plus the single-pass fused sparse-attention
megakernel (+ jnp oracles)."""

from . import ops, ref

__all__ = ["ops", "ref"]
