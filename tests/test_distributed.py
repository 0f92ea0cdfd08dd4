"""Distributed tests.  Mesh-requiring cases run in SUBPROCESSES (via the
shared ``run_sub`` conftest fixture) so the host-device-count flag never
leaks into the rest of the suite (per the dry-run isolation requirement)."""
import jax
import jax.numpy as jnp

from repro.distributed.sharding import fleet_sharding, gspmd_lowering, pspec
from repro.launch.mesh import make_fleet_mesh


# ------------------------------------------------------------------ pspec
def test_pspec_greedy_rules():
    names = ("pod", "data", "model")
    sizes = {"pod": 2, "data": 16, "model": 16}
    assert pspec((256, 4096), ("batch", None), names, sizes) \
        == jax.sharding.PartitionSpec(("pod", "data"), None)
    # kv_heads=8 indivisible by model=16 → falls through; head takes it
    assert pspec((128, 32768, 8, 128),
                 ("batch_full", "kv_seq", "kv_heads", "head"),
                 names, sizes)[2] is None
    assert pspec((128, 32768, 8, 128),
                 ("batch_full", "kv_seq", "kv_heads", "head"),
                 names, sizes)[3] == "model"
    # each mesh axis used at most once per tensor
    sp = pspec((64, 64), ("vocab", "ff"), names, sizes)
    assert sp == jax.sharding.PartitionSpec("model", None)


def test_pspec_single_device_mesh_noop():
    assert pspec((8, 8), ("batch", "vocab"), ("data", "model"),
                 {"data": 1, "model": 1}) \
        == jax.sharding.PartitionSpec(None, None)


def test_gspmd_lowering_switches_off_shardy_inside_only():
    """The scoped partitioner switch rests on a private JAX config state;
    this fails when that state goes away or stops taking effect."""
    f = jax.jit(lambda x: 2 * x,
                in_shardings=fleet_sharding(make_fleet_mesh(1)))
    was = jax.config.jax_use_shardy_partitioner
    with gspmd_lowering():
        assert jax.config.jax_use_shardy_partitioner is False
        text = f.lower(jnp.ones(4)).as_text()
        assert "sdy." not in text and "mhlo.sharding" in text
    assert jax.config.jax_use_shardy_partitioner == was


# -------------------------------------------------------------- lowering
def test_train_step_lowers_on_smoke_mesh(run_sub):
    out = run_sub("""
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.shapes import ShapeCell, build_cell
        cfg = get_config("llama3.2-3b").reduced().replace(
            dtype="float32", attn_chunk=16)
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        cell = ShapeCell("mini_train", "train", 32, 8)
        with jax.set_mesh(mesh):
            step, args, shards, outs, donate = build_cell(
                cfg, cell, mesh, grad_accum=2)
            c = jax.jit(step, in_shardings=shards, out_shardings=outs,
                        donate_argnums=donate).lower(*args).compile()
        print("COMPILED", c.memory_analysis().temp_size_in_bytes)
    """)
    assert "COMPILED" in out


def test_decode_lowers_on_smoke_mesh(run_sub):
    out = run_sub("""
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.shapes import ShapeCell, build_cell
        cfg = get_config("recurrentgemma-9b").reduced().replace(
            dtype="float32", attn_chunk=16)
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        cell = ShapeCell("mini_decode", "decode", 64, 8)
        with jax.set_mesh(mesh):
            step, args, shards, outs, donate = build_cell(cfg, cell, mesh)
            c = jax.jit(step, in_shardings=shards, out_shardings=outs,
                        donate_argnums=donate).lower(*args).compile()
        print("COMPILED")
    """)
    assert "COMPILED" in out


def test_moe_sharded_matches_unsharded(run_sub):
    """EP shard_map output == single-device reference (same params/input)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.moe import apply_moe, init_moe
        cfg = get_config("dbrx-132b").reduced().replace(
            dtype="float32", moe_capacity_factor=100.0)
        key = jax.random.PRNGKey(0)
        p = init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(key, (4, 8, cfg.d_model), jnp.float32)
        y_ref, aux_ref = apply_moe(p, cfg, x)        # no mesh: local path
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            y_sh, aux_sh = jax.jit(lambda p, x: apply_moe(p, cfg, x))(p, x)
        err = float(jnp.max(jnp.abs(y_ref - y_sh)))
        print("ERR", err, float(aux_ref), float(aux_sh))
        assert err < 1e-4, err
    """)
    assert "ERR" in out


def test_sharded_ce_matches_unsharded(run_sub):
    """Vocab-sharded cross-entropy == plain CE."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import lm
        cfg = get_config("llama3.2-3b").reduced().replace(
            dtype="float32", attn_chunk=16)
        key = jax.random.PRNGKey(0)
        params = lm.init_params(key, cfg)
        toks = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        tgts = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "targets": tgts}
        ref = float(jax.jit(lambda p, b: lm.lm_loss(p, cfg, b))(params,
                                                                batch))
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            sh = float(jax.jit(lambda p, b: lm.lm_loss(p, cfg, b))(params,
                                                                   batch))
        print("LOSSES", ref, sh)
        assert abs(ref - sh) < 1e-4
    """)
    assert "LOSSES" in out


def test_elastic_restore_across_meshes(run_sub):
    """Checkpoint on a (2,4) mesh, restore on (4,2) — values identical."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.manager import CheckpointManager
        from repro.launch.mesh import make_smoke_mesh
        m1 = make_smoke_mesh((2, 4), ("data", "model"))
        m2 = make_smoke_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        x1 = jax.device_put(x, NamedSharding(m1, P("data", "model")))
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"x": x1}, block=True)
            restored = mgr.restore(
                1, {"x": x},
                shardings={"x": NamedSharding(m2, P("model", "data"))})
            np.testing.assert_array_equal(np.asarray(restored["x"]),
                                          np.asarray(x))
            print("ELASTIC_OK", restored["x"].sharding.spec)
    """)
    assert "ELASTIC_OK" in out


def test_grad_compression_bf16_shrinks_accumulator(run_sub):
    """bf16 grad accumulation halves the gradient-accumulator footprint.

    Verified structurally on the compiled HLO: with compression the scan
    carry / collectives materialize bf16 buffers, without it (f32 model)
    the program contains none.  (Total temp bytes are NOT asserted — at
    smoke scale XLA's cast scratch outweighs the accumulator saving and
    the accounting shifts between backend versions.)"""
    out = run_sub("""
        import jax
        from repro.configs import get_config
        from repro.launch.mesh import make_smoke_mesh
        from repro.launch.shapes import ShapeCell, build_cell
        from repro.train.optim import OptimConfig
        cfg = get_config("llama3.2-3b").reduced().replace(
            dtype="float32", attn_chunk=16)
        mesh = make_smoke_mesh((4, 2), ("data", "model"))
        cell = ShapeCell("mini_train", "train", 32, 8)
        nbf16 = {}
        for mode in ("none", "bf16"):
            oc = OptimConfig(grad_compression=mode, shard_grads=False)
            with jax.set_mesh(mesh):
                step, args, shards, outs, donate = build_cell(
                    cfg, cell, mesh, opt_cfg=oc, grad_accum=4)
                comp = jax.jit(step, in_shardings=shards,
                               out_shardings=outs,
                               donate_argnums=donate).lower(*args).compile()
            nbf16[mode] = comp.as_text().count("bf16[")
        print("BF16_BUFS", nbf16["none"], nbf16["bf16"])
        assert nbf16["none"] == 0, nbf16
        assert nbf16["bf16"] > 0, nbf16
    """)
    assert "BF16_BUFS" in out
