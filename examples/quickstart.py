"""Quickstart: the FlashSparse public API in 60 lines.

  PYTHONPATH=src python examples/quickstart.py

Covers: building ME-BCRS from COO, SpMM/SDDMM through the XLA and Pallas
paths, the sparse-softmax composition (SDDMM → softmax → SpMM, the AGNN
attention pattern), and the redundancy metrics that motivate the paper.
"""

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (
    block_format, from_coo, mma_count, sddmm, spmm, summarize, to_dense,
    with_values, zeros_in_nonzero_vectors,
)
from repro.core.softmax import sparse_softmax
from repro.launch.cache import enable_compile_cache
from repro.sparse.graphs import make_dataset

enable_compile_cache()

# 1. a scaled replica of the paper's GitHub graph ---------------------------
g = make_dataset("GitHub", scale=0.02)
shape = (g.num_nodes, g.num_nodes)
print(f"graph: {g.num_nodes:,} nodes, {g.num_edges:,} edges")

# 2. translate to ME-BCRS at the paper's two granularities ------------------
f8 = from_coo(g.rows, g.cols, g.vals, shape, vector_size=8)
f16 = from_coo(g.rows, g.cols, g.vals, shape, vector_size=16)
print(f"8x1  vectors: {f8.nnzv:,}  carried zeros: {zeros_in_nonzero_vectors(f8):,}")
print(f"16x1 vectors: {f16.nnzv:,}  carried zeros: {zeros_in_nonzero_vectors(f16):,}")
print(f"MMA invocations (N=16): 16x1 = {mma_count(f16, 16):,} "
      f"vs 8x1 = {mma_count(f8, 16):,}")

# 3. SpMM: sparse adjacency @ dense features --------------------------------
feats = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((g.num_nodes, 64)).astype(np.float32))
out_xla = spmm(f8, feats, impl="blocked")          # XLA path
blocked = block_format(f8, k_blk=8)
from repro.kernels import ops
out_pallas = ops.spmm(blocked, feats)              # Pallas kernel (interpret)
np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_pallas),
                           rtol=1e-4, atol=1e-4)
print("SpMM: XLA blocked path == Pallas kernel  ✓")

# 4. SDDMM → sparse softmax → SpMM (the AGNN attention pattern) -------------
scores = sddmm(f8, feats, feats)                   # sampled QK^T at A's pattern
probs = sparse_softmax(blocked, scores)            # row softmax, blocked layout
attended = spmm(with_values(blocked, probs), feats)
print(f"AGNN attention pipeline: out {attended.shape}, "
      f"finite: {bool(jnp.all(jnp.isfinite(attended)))}")

# 5. the paper's redundancy story in one dict -------------------------------
print("\nredundancy summary (8x1):")
for k, v in summarize(f8, 128).items():
    print(f"  {k:18s} {v:,.0f}" if isinstance(v, (int, float)) else f"  {k}: {v}")
