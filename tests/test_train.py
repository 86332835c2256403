"""Optimizer, checkpointing, trainer loop, gradient compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as C
from repro.train import compression as GC
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   clip_by_global_norm, init_opt_state,
                                   schedule_lr)


def test_wsd_schedule_phases():
    cfg = OptimizerConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                          total_steps=100, decay_frac=0.2, min_lr_frac=0.1)
    lr = lambda s: float(schedule_lr(cfg, jnp.int32(s)))  # noqa: E731
    assert lr(0) == pytest.approx(0.0)
    assert lr(5) == pytest.approx(0.5)          # warmup
    assert lr(10) == pytest.approx(1.0)
    assert lr(50) == pytest.approx(1.0)          # stable plateau
    assert lr(79) == pytest.approx(1.0, abs=0.06)
    assert lr(90) == pytest.approx(0.55, abs=0.02)  # mid decay
    assert lr(100) == pytest.approx(0.1, abs=0.01)  # floor


def test_cosine_schedule_monotone_decay():
    cfg = OptimizerConfig(lr=1.0, schedule="cosine", warmup_steps=5, total_steps=50)
    lrs = [float(schedule_lr(cfg, jnp.int32(s))) for s in range(5, 51, 5)]
    assert all(a >= b - 1e-6 for a, b in zip(lrs, lrs[1:]))


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_adamw_converges_quadratic():
    """AdamW should minimize a simple quadratic — catches sign/bias bugs."""
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(lr=0.1, weight_decay=0.0, grad_clip=100.0,
                          schedule="const", warmup_steps=1)
    for _ in range(300):
        g = {"w": 2 * (params["w"] - target)}
        params, opt, _ = adamw_update(cfg, g, opt, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=1e-2)


def test_adamw_bf16_state_roundtrip():
    params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    opt = init_opt_state(params, dtype=jnp.bfloat16)
    cfg = OptimizerConfig(lr=1e-2)
    g = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    p2, o2, _ = adamw_update(cfg, g, opt, params)
    assert p2["w"].dtype == jnp.bfloat16
    assert o2["m"]["w"].dtype == jnp.bfloat16


# --- checkpoint ---------------------------------------------------------


def _tiny_state():
    k = jax.random.PRNGKey(0)
    params = {"emb": {"table": jax.random.normal(k, (8, 4))},
              "units": {"w": jax.random.normal(k, (3, 4, 4))}}
    return params, init_opt_state(params)


def test_checkpoint_roundtrip():
    params, opt = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 7, params=params, opt_state=opt, extra={"note": "x"})
        like = {"params": params, "opt_state": opt}
        out = C.restore(d, 7, like=like)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
                     out["params"], params)
        assert out["step"] == 7
        assert out["extra"]["note"] == "x"


def test_checkpoint_retention_and_latest():
    params, opt = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            C.save(d, s, params=params, opt_state=opt, keep=2)
        assert C.available_steps(d) == [3, 4]
        out = C.restore_latest(d, like={"params": params, "opt_state": opt})
        assert out["step"] == 4


def test_checkpoint_atomicity_no_tmp_left():
    params, opt = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 1, params=params, opt_state=opt)
        assert not any(f.endswith(".tmp") for f in os.listdir(d))


def test_elastic_restore_reshard():
    """Restore a checkpoint and re-shard onto a (1-device) different mesh —
    the elastic path; on a pod the same call re-shards onto survivors."""
    from repro.train.elastic import choose_mesh_shape, make_mesh_from_devices, remesh_state
    params, opt = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 3, params=params, opt_state=opt)
        out = C.restore(d, 3, like={"params": params, "opt_state": opt})
        shape = choose_mesh_shape(len(jax.devices()))
        mesh = make_mesh_from_devices(jax.devices(), shape)
        state = remesh_state(out, params, mesh)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
                     state["params"], params)


def test_choose_mesh_shape_degrades():
    from repro.train.elastic import choose_mesh_shape
    assert choose_mesh_shape(256) == (16, 16)
    assert choose_mesh_shape(240, prefer_model=16) == (15, 16)
    assert choose_mesh_shape(7) == (1, 7)


# --- straggler detection ---------------------------------------------------


def test_heartbeat_flags_stragglers():
    from repro.train.elastic import ElasticPolicy, Heartbeat
    hb = Heartbeat(factor=3.0)
    for s in range(10):
        hb.beat(s, 0.1)
    assert not hb.is_straggling()
    hb.beat(10, 0.9)
    assert hb.is_straggling()
    pol = ElasticPolicy(tolerate_flags=3)
    for s in (11, 12):
        hb.beat(s, 0.9)
    assert pol.should_remesh(hb) or len(hb.flagged) >= 3


# --- gradient compression ----------------------------------------------------


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, s = GC.quantize_int8(x)
    err = jnp.abs(GC.dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) / 2 + 1e-6


def test_error_feedback_unbiased_over_steps():
    """With error feedback, the accumulated compressed sum converges to the
    accumulated true sum (residual stays bounded)."""
    g = jax.random.normal(jax.random.PRNGKey(1), (256,)) * 1e-3
    r = jnp.zeros(256)
    acc = jnp.zeros(256)
    for _ in range(50):
        q, s, r = GC.compress_residual(g, r)
        acc = acc + GC.dequantize_int8(q, s)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(50 * g), atol=2 * float(s))


def test_psum_compressed_single_device():
    """shard_map psum of the compressed gradient == plain mean on 1 device."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (64,))}
    r = GC.init_residuals(g)

    def f(g, r):
        return GC.psum_compressed(g, r, "dp")

    out, r2 = shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))(g, r)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]), atol=2e-2)


def test_trainer_loss_decreases():
    from repro.configs import reduced
    from repro.data.pipeline import pipeline_for
    from repro.models.registry import Model, get_config
    from repro.train.trainer import TrainLoop, TrainLoopConfig
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg)
    with tempfile.TemporaryDirectory() as d:
        loop = TrainLoop(model, OptimizerConfig(lr=3e-3, warmup_steps=3, total_steps=30),
                         TrainLoopConfig(total_steps=30, log_every=30, ckpt_every=30,
                                         ckpt_dir=d),
                         pipeline_for(cfg, shape_batch=4, seq_len=64))
        loop.run(resume=False)
        # compare first/last logged loss
        losses = [l for (_, l, _) in loop.history]
        assert losses[-1] < 5.56  # below random-init CE (ln 256 = 5.545 + margin)


def test_microbatch_accumulation_matches_full_batch():
    from repro.configs import reduced, smoke_batch
    from repro.models.registry import Model, get_config
    from repro.train.trainer import make_train_step
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    batch = smoke_batch(cfg, batch=4, seq=32)
    ocfg = OptimizerConfig(lr=1e-3)
    s1 = make_train_step(model, ocfg, microbatches=1, donate=False)
    s2 = make_train_step(model, ocfg, microbatches=2, donate=False)
    p1, _, m1 = s1(params, opt, batch)
    p2, _, m2 = s2(params, opt, batch)
    # losses equal; params close (grad mean over microbatches == full grad)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    assert max(jax.tree.leaves(d)) < 5e-5
