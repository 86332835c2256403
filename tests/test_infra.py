"""Sharding rules, HLO parsing, jaxpr flop counting, data pipeline, serving,
SparseLinear, microbenchmark generators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import reduced
from repro.models.registry import Model, get_config
from repro.sharding import rules as R


def _mesh(shape=(16, 16), names=("data", "model")):
    try:
        return AbstractMesh(shape, names)
    except TypeError:  # older jax: AbstractMesh(((name, size), ...))
        return AbstractMesh(tuple(zip(names, shape)))


def test_param_rules_qwen3():
    model = Model(get_config("qwen3-0.6b"))
    specs = R.param_specs(model.param_shapes(), _mesh())
    assert specs["embed"]["table"] == P("model", None)
    # stacked units: leading layer axis unsharded, head dim sharded
    assert specs["units"]["attn"]["wq"] == P(None, None, "model")
    assert specs["units"]["attn"]["wo"] == P(None, "model", None)
    assert specs["units"]["mlp"]["wi_gate"] == P(None, None, "model")
    assert specs["units"]["ln_attn"]["scale"] == P(None, None)  # (L, D) stacked


def test_param_rules_divisibility_fallback():
    """glm4 has 2 KV heads: wk out-dim = 256 on a 16-way model axis is fine
    (256 % 16 == 0), but a 24-wide dim on 16 would fall back to replicated."""
    mesh = _mesh()
    fb = []
    spec = R._resolve(("tp",), (24,), mesh, fb, "x")
    assert spec == P(None) and fb


def test_zero1_adds_dp_axis():
    model = Model(get_config("qwen3-0.6b"))
    shapes = model.param_shapes()
    z = R.zero1_specs(shapes, _mesh())
    s = z["units"]["mlp"]["wi_gate"]
    assert "data" in str(s)  # dp sharding added on a replicated dim


def test_moe_expert_parallel_specs():
    model = Model(get_config("moonshot-v1-16b-a3b"))
    specs = R.param_specs(model.param_shapes(), _mesh())
    assert specs["units"]["moe"]["wi_gate"] == P(None, "model", None, None)


def test_cache_specs_kv_vs_ssm():
    mesh = _mesh()
    kv = {"k": jax.ShapeDtypeStruct((128, 32768, 16, 128), jnp.bfloat16)}
    s = R.cache_specs(kv, mesh)
    assert s["k"] == P("data", None, "model", None)
    ssm = {"ssm": jax.ShapeDtypeStruct((128, 80, 64, 128), jnp.float32)}
    s2 = R.cache_specs(ssm, mesh)
    assert s2["ssm"] == P("data", "model", None, None)
    # long-context unshardable heads -> sequence parallel
    kv_long = {"k": jax.ShapeDtypeStruct((1, 524288, 8, 128), jnp.bfloat16)}
    s3 = R.cache_specs(kv_long, mesh)
    assert s3["k"] == P(None, "model", None, None)


def test_batch_specs():
    mesh = _mesh()
    b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    assert R.batch_specs(b, mesh)["tokens"] == P("data", None)
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 4096), jnp.int32)}
    assert R.batch_specs(b1, mesh)["tokens"] == P()


# --- HLO utils ----------------------------------------------------------


def test_shape_bytes():
    from repro.utils.hlo import shape_bytes
    assert shape_bytes("f32[128,1024]") == 128 * 1024 * 4
    assert shape_bytes("bf16[2,16]") == 64
    assert shape_bytes("f32[]") == 4
    assert shape_bytes("pred[7]") == 7


def test_parse_collectives_synthetic():
    from repro.utils.hlo import parse_collectives
    hlo = """
  %ag = f32[64,128] all-gather(f32[4,128] %x), replica_groups={}
  %ar.1 = bf16[1024] all-reduce(bf16[1024] %y), to_apply=%add
  %rs = f32[8] reduce-scatter(f32[128] %z), dimensions={0}
  %cp = f32[32] collective-permute(f32[32] %w), source_target_pairs={{0,1}}
  %ag2 = f32[64] all-gather-start(f32[4] %v)
  %agd = f32[64] all-gather-done(f32[64] %ag2)
"""
    st = parse_collectives(hlo)
    assert st.count_by_kind["all-gather"] == 2  # -start counted, -done not
    assert st.bytes_by_kind["all-reduce"] == 2048
    assert st.bytes_by_kind["reduce-scatter"] == 32
    assert st.total_count == 5


def test_parse_collectives_real_psum():
    from repro.utils.hlo import parse_collectives
    from jax import shard_map
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))
    f = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    txt = jax.jit(f).lower(jnp.ones((64,))).compile().as_text()
    st = parse_collectives(txt)
    assert st.count_by_kind.get("all-reduce", 0) >= 1


# --- jaxpr flops ------------------------------------------------------------


def test_jaxpr_flops_matmul_exact():
    from repro.utils.jaxpr_flops import flops_of_fn
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    assert flops_of_fn(lambda a, b: a @ b, a, b) == 2 * 64 * 128 * 32


def test_jaxpr_flops_scan_multiplies():
    from repro.utils.jaxpr_flops import flops_of_fn
    def f(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, ()), x, ws)[0]
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 16, 16), jnp.float32)
    fl = flops_of_fn(f, x, ws)
    assert fl >= 5 * 2 * 8 * 16 * 16


def test_jaxpr_flops_remat_counts_recompute():
    from repro.utils.jaxpr_flops import flops_of_fn
    def loss(w, x):
        f = jax.checkpoint(lambda x, w: jnp.tanh(x @ w))
        return jnp.sum(f(x, w))
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    fwd = flops_of_fn(loss, w, x)
    bwd = flops_of_fn(lambda w, x: jax.grad(loss)(w, x), w, x)
    assert 3.0 < bwd / fwd < 5.0  # fwd + recompute + 2x bwd matmuls


# --- data pipeline ------------------------------------------------------------


def test_pipeline_deterministic_skip_ahead():
    from repro.data.pipeline import PipelineConfig, TokenPipeline
    cfg = PipelineConfig(vocab=1000, seq_len=16, global_batch=4, seed=7)
    p1 = TokenPipeline(cfg)
    p2 = TokenPipeline(cfg)
    p2.skip_to(5)
    for _ in range(5):
        p1.next_batch()
    b1, b2 = p1.next_batch(), p2.next_batch()
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].max() < 1000
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_pipeline_host_sharding():
    from repro.data.pipeline import PipelineConfig, TokenPipeline
    full = TokenPipeline(PipelineConfig(vocab=100, seq_len=8, global_batch=8, seed=1))
    assert full.next_batch()["tokens"].shape == (8, 8)
    shard = TokenPipeline(PipelineConfig(vocab=100, seq_len=8, global_batch=8,
                                         seed=1, host_index=1, host_count=2))
    assert shard.next_batch()["tokens"].shape == (4, 8)


# --- serving --------------------------------------------------------------------


def test_engine_greedy_deterministic():
    from repro.serve.engine import Engine, GenerationConfig
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=48)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    out1 = eng.generate(prompts, GenerationConfig(max_new_tokens=6))
    eng2 = Engine(model, params, batch_size=2, max_len=48)
    out2 = eng2.generate(prompts, GenerationConfig(max_new_tokens=6))
    assert out1 == out2
    assert all(len(o) == 6 for o in out1)


def test_slot_manager():
    from repro.serve.kv_cache import SlotManager
    sm = SlotManager(2, 64)
    assert sm.admit(0, 8) == 0 and sm.admit(1, 8) == 1
    assert sm.admit(2, 8) is None  # full
    sm.record_token(0, 5, eos_id=5, max_new=10)
    assert sm.slots[0].done
    assert sm.admit(2, 8) == 0  # freed slot reused


# --- SparseLinear -----------------------------------------------------------------


def test_sparse_linear_bsr_matches_dense():
    from repro.models.sparse import SparseLinear, magnitude_prune
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 256)).astype(np.float32)
    w = magnitude_prune(w, 0.25, structured=(8, 128))
    lin = SparseLinear.from_dense(w, fmt="bsr", backend="ref")
    x = jnp.asarray(rng.standard_normal((4, 256)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(lin(x)), np.asarray(x) @ w.T,
                               rtol=2e-4, atol=2e-4)


def test_sparse_linear_sell_matches_dense():
    from repro.models.sparse import SparseLinear, magnitude_prune
    rng = np.random.default_rng(1)
    w = magnitude_prune(rng.standard_normal((48, 96)).astype(np.float32), 0.1)
    lin = SparseLinear.from_dense(w, fmt="sell", backend="ref")
    x = jnp.asarray(rng.standard_normal((3, 96)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(lin(x)), np.asarray(x) @ w.T,
                               rtol=2e-4, atol=2e-4)


def test_advisor_block_vs_unstructured():
    from repro.models.sparse import advise_weight_format, magnitude_prune
    rng = np.random.default_rng(2)
    w = rng.standard_normal((64, 512)).astype(np.float32)
    w_block = magnitude_prune(w, 0.2, structured=(8, 128))
    w_rand = magnitude_prune(w, 0.05)
    assert advise_weight_format(w_block, (8, 128)) == "bsr"
    assert advise_weight_format(w_rand, (8, 128)) == "sell"


# --- microbench generators ----------------------------------------------------------


def test_bernoulli_mean_stride():
    from repro.core.microbench import ind_random_bernoulli, stride_stats
    idx = ind_random_bernoulli(200_000, k=8.0, seed=0)
    st = stride_stats(idx)
    assert st["mean_stride"] == pytest.approx(8.0, rel=0.1)
    # paper: variance grows as k(k-1)
    assert st["var_stride"] == pytest.approx(8 * 7, rel=0.25)


def test_gaussian_strides_backward_jumps():
    from repro.core.microbench import ind_gaussian, stride_stats
    idx = ind_gaussian(50_000, mean=4, var=100.0, n_b=10**6, seed=0)
    st = stride_stats(idx)
    assert st["frac_backward"] > 0.1  # negative strides present at high variance
    idx2 = ind_gaussian(50_000, mean=16, var=0.0, n_b=10**7, seed=0)
    assert stride_stats(idx2)["frac_backward"] == 0.0


def test_microbench_kernels_match_numpy():
    import repro.core.microbench as MB
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    B = jnp.asarray(rng.standard_normal(8000).astype(np.float32))
    ind = jnp.asarray(MB.ind_constant_stride(1000, 8, 8000))
    np.testing.assert_allclose(float(MB.isscp(A, B, ind)),
                               float(np.dot(np.asarray(A), np.asarray(B)[::8][:1000])),
                               rtol=1e-4)
