"""Multi-device sharding rules + dry-run machinery (subprocess-isolated).

The main pytest process must keep the single real CPU device (per brief),
so everything needing a multi-device mesh runs in a child process with
``--xla_force_host_platform_device_count`` pinned before jax import.
"""

from _child import run_child


def test_param_sharding_rules():
    out = run_child("""
        import jax, json
        import numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_reduced
        from repro.distributed.sharding import (
            fit_pspec, param_shardings, shardings_like)
        from repro.models.lm import init_lm

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        # divisibility: vocab 512 % 2 == 0 -> sharded; odd dim -> dropped
        assert tuple(fit_pspec(("vocab", "embed"), (512, 128), mesh)) \\
            == ("model", "data")
        assert tuple(fit_pspec(("vocab", None), (511, 128), mesh)) == ()

        cfg = get_reduced("granite-3-2b")
        shapes = jax.eval_shape(lambda: init_lm(jax.random.key(0), cfg))
        sh = param_shardings(shapes, mesh)
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
        flat_sh = jax.tree_util.tree_leaves(sh)
        by_name = {}
        for (kp, leaf), s in zip(flat, flat_sh):
            name = "/".join(str(getattr(k, "key", k)) for k in kp)
            by_name[name] = (leaf.shape, tuple(s.spec))
        # stacked attn weight: (L, d, H*hd) -> (None, data, model)
        assert by_name["layers/attn/wq"][1] == (None, "data", "model")
        # norms replicated
        assert by_name["final_norm"][1] == ()
        # vocab sharding on embed applied iff divisible
        v = cfg.vocab
        expect = ("model", "data") if v % 2 == 0 else (None, "data")
        assert by_name["embed"][1] == expect, by_name["embed"]
        print("PARAM_RULES_OK")
    """, timeout=60)
    assert "PARAM_RULES_OK" in out


def test_cache_sharding_rules():
    out = run_child("""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.distributed.sharding import cache_shardings
        from repro.models.lm import init_cache

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        cfg = get_config("granite-8b")  # kv=8 heads: divisible by model=2
        cache = jax.eval_shape(lambda: init_cache(cfg, 8, capacity=64))
        sh = cache_shardings(cache, mesh, batch=8)
        spec_k = tuple(sh["layers"]["k"].spec)
        # batch over data; heads over model (preferred over seq)
        assert spec_k[:4] == (None, "data", None, "model"), spec_k

        # batch=1 (long-context): sequence-parallel over everything
        cache1 = jax.eval_shape(lambda: init_cache(cfg, 1, capacity=64))
        sh1 = cache_shardings(cache1, mesh, batch=1)
        spec1 = tuple(sh1["layers"]["k"].spec)
        assert spec1[2] in ("data", ("data", "model")), spec1
        print("CACHE_RULES_OK")
    """, timeout=60)
    assert "CACHE_RULES_OK" in out


def test_elastic_reshard_roundtrip():
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_reduced
        from repro.distributed.elastic import reshard_state
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import TrainStepConfig, init_train_state

        cfg = get_reduced("qwen3-0.6b")
        ts = TrainStepConfig(opt=AdamWConfig())
        state = init_train_state(jax.random.key(0), cfg, ts)

        mesh_a = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        mesh_b = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
        sa = reshard_state(state, mesh_a)   # healthy mesh
        sb = reshard_state(sa, mesh_b)      # degraded mesh (node loss)
        for x, y in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(sb["params"])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("ELASTIC_OK")
    """, timeout=60)
    assert "ELASTIC_OK" in out


def test_tiny_dryrun_cell_compiles():
    """plan→lower→compile→roofline on a reduced arch with an 8-device mesh
    — the dry-run machinery end to end, small enough for CI."""
    out = run_child("""
        import dataclasses, jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.launch import cells as C
        from repro.configs import SHAPES

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        small = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                     d_ff=128, vocab=256, head_dim=16)
        # shrink the shape too
        SHAPES["train_4k"] = dataclasses.replace(
            SHAPES["train_4k"], seq_len=64, global_batch=8)
        res = C.account_cell("granite-3-2b", "train_4k", mesh, "m4x2",
                             cfg_overrides=small)
        r = res.report
        assert r.per_device_flops > 0 and r.per_device_bytes > 0
        assert r.bottleneck in ("compute", "memory", "collective")
        assert res.memory_stats["temp_bytes"] >= 0
        print("DRYRUN_OK", r.bottleneck)
    """, devices=8, timeout=60)
    assert "DRYRUN_OK" in out


def test_moe_ep_matches_dense_path():
    """Expert-parallel shard_map dispatch == global-sort dispatch (dropless)."""
    out = run_child("""
        import dataclasses, jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_reduced
        from repro.distributed.ctx import activation_mesh
        from repro.models.layers import init_moe, moe_ffn

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
        cfg = get_reduced("moonshot-v1-16b-a3b", capacity_factor=4.0)
        # reduced: 4 experts, top-2 -> e % model(4) == 0
        p = init_moe(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model),
                              cfg.dtype)

        ref, aux_ref = jax.jit(lambda p, x: moe_ffn(p, x, cfg))(p, x)

        cfg_ep = dataclasses.replace(cfg, moe_ep=True)
        with mesh, activation_mesh(mesh):
            ep, aux_ep = jax.jit(lambda p, x: moe_ffn(p, x, cfg_ep))(p, x)
        np.testing.assert_allclose(np.asarray(ref, np.float32),
                                   np.asarray(ep, np.float32),
                                   rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=1e-3)
        print("MOE_EP_OK")
    """, timeout=60)
    assert "MOE_EP_OK" in out


def test_collective_matmul_matches_dot():
    out = run_child("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
        from repro.distributed.collective_matmul import collective_matmul

        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32))
        with mesh:
            y = collective_matmul(x, w, mesh, "data", "model")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)
        print("CM_OK")
    """, timeout=60)
    assert "CM_OK" in out
