"""Forward parity of the overlapped sharded path, as one child program.

The double-buffered ``ppermute`` ring (SpMM at ``n_batches`` 1, 2 and 4,
SDDMM, attention, stacked SpMM) must match the single-device
``pallas_balanced`` path (fp32, ``rtol=atol=2e-5``) on three matrices:
uniform, hub row, and all-empty windows with ragged N.

One child with 8 host devices draws the operands and computes each
single-device reference once, then checks every mesh it is given
(``make_host_mesh`` takes the first ``data * model`` devices).  Each
mesh's checks sit in their own ``try``: the child prints one
``OVERLAP_PARITY_OK data model`` or ``OVERLAP_PARITY_FAIL data model
<error>`` line per mesh.  The parametrised cases that read those lines
are in ``tests/test_sparse_shard_overlap_parity_*.py``.

Each ring call is traced and compiled as one program (``jax.jit``).
Called eagerly, ``shard_map`` compiles every op of the ring on its own:
about 400 compiles and 30 s per call on a 4x2 mesh, against 3–4 s
jitted, which made these checks about 1,000 s of the tier-1 run.  The
partitions are built before tracing: ``sharded_schedule`` memoizes them
on the format, and one built inside a trace would hand its tracers to
the next call.  Eager calls of the ring stay checked on a 4x2 mesh by
``test_overlap_gradients_match_sharded`` and
``test_overlap_precision_ladder``, and on a 1x1 mesh by the conformance
suite.
"""

from _child import run_child

_PROGRAM = """
    import traceback
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import from_dense, block_format
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.sparse_shard import sharded_schedule
    from repro.distributed.sparse_shard_overlap import (
        attention_sharded_overlap, sddmm_sharded_overlap,
        spmm_sharded_overlap)

    rng = np.random.default_rng(0)
    mats = []
    for seed, hub, m in [(0, False, 64), (1, True, 64), (2, False, 24)]:
        a = ((rng.random((m, m)) < 0.1)
             * rng.standard_normal((m, m))).astype(np.float32)
        if hub:
            a[5, :] = rng.standard_normal(m) * (rng.random(m) < 0.8)
        if seed == 2:
            a[:] = 0.0          # all-empty windows
        mats.append(a)
    # operands and single-device references, drawn and computed once
    cases = []
    for a in mats:
        m = a.shape[0]
        blocked = block_format(from_dense(a), 8)
        # ragged N (not a multiple of n_blk) on purpose
        b = jnp.asarray(rng.standard_normal((m, 20)).astype(np.float32))
        q = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((m, 16)).astype(np.float32))
        q3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))
        v3 = jnp.asarray(rng.standard_normal((2, m, 16)).astype(np.float32))
        b3 = jnp.stack([b, 2 * b])
        refs = (ops.spmm_balanced(blocked, b, interpret=True),
                ops.sddmm_balanced(blocked, q, k, interpret=True),
                ops.attention_balanced(blocked, q3, k, v3, interpret=True),
                ops.spmm_balanced(blocked, b3, interpret=True))
        cases.append((blocked, b, q, k, q3, v3, b3, refs))

    def close(out, ref):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def jitted(op, blocked, mesh, *xs, **kw):
        return jax.jit(lambda *ys: op(blocked, *ys, mesh=mesh, **kw))(*xs)

    for data, model in {meshes}:
        try:
            mesh = make_host_mesh(data, model)
            for blocked, b, q, k, q3, v3, b3, refs in cases:
                for nb in (1, 2, 4):
                    sharded_schedule(blocked, data, n_batches=nb)
                sharded_schedule(blocked, data, window_split=False,
                                 n_batches=2)
                for nb in (1, 2, 4):
                    close(jitted(spmm_sharded_overlap, blocked, mesh, b,
                                 n_batches=nb), refs[0])
                close(jitted(sddmm_sharded_overlap, blocked, mesh, q, k,
                             n_batches=2), refs[1])
                # batched heads (H=2) through the window-aligned
                # megakernel path
                close(jitted(attention_sharded_overlap, blocked, mesh, q3,
                             k, v3, n_batches=2), refs[2])
                # stacked dense operand (H=2 SpMM)
                close(jitted(spmm_sharded_overlap, blocked, mesh, b3,
                             n_batches=2), refs[3])
            print("OVERLAP_PARITY_OK", data, model, flush=True)
        except Exception as e:
            traceback.print_exc()
            print("OVERLAP_PARITY_FAIL", data, model,
                  " ".join(str(e).split()), flush=True)
"""


def run_overlap_parity(meshes, *, timeout: float) -> str:
    """Run the parity program on ``meshes`` ((data, model) pairs)."""
    return run_child(_PROGRAM.format(meshes=list(meshes)), devices=8,
                     timeout=timeout)
