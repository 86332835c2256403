"""SpMVPlan: round-trip correctness, cached preprocessing, block autotuning,
plan-aware consumers (eigensolver, serving, distributed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.core import perfmodel as PM
from repro.core import spmv as S
from repro.core.matrices import block_sparse_dense, holstein_hubbard_surrogate, random_sparse
from repro.core.plan import SpMVPlan, plan_all_formats

PLAN_FORMATS = [("csr", {}), ("ell", {}), ("jds", {}), ("sell", dict(C=8)),
                ("sell", dict(C=16, sigma=32, sort_cols=True)), ("hybrid", {})]


def _rand_x(n, seed=3, k=None, dtype=np.float32):
    rng = np.random.default_rng(seed)
    shape = (n,) if k is None else (n, k)
    return rng.standard_normal(shape).astype(dtype)


# --- round-trip correctness -------------------------------------------------

@pytest.mark.parametrize("fmt,kw", PLAN_FORMATS)
def test_plan_matches_reference_spmv(hh_small, fmt, kw):
    obj = F.convert(hh_small, fmt, **kw)
    x = jnp.asarray(_rand_x(hh_small.shape[1]))
    y_plan = np.asarray(SpMVPlan.compile(obj)(x))
    y_ref = np.asarray(S.spmv(hh_small, x))
    np.testing.assert_allclose(y_plan, y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("fmt,kw", PLAN_FORMATS)
def test_plan_spmm_matches_stacked_spmv(hh_small, fmt, kw):
    obj = F.convert(hh_small, fmt, **kw)
    X = jnp.asarray(_rand_x(hh_small.shape[1], k=5))
    Y = np.asarray(SpMVPlan.compile(obj).spmm(X))
    plan = SpMVPlan.compile(obj)
    cols = np.stack([np.asarray(plan(X[:, j])) for j in range(5)], axis=1)
    np.testing.assert_allclose(Y, cols, rtol=2e-5, atol=2e-5)


def test_plan_synthetic_matrices():
    for seed in (0, 1):
        m = random_sparse(80, 64, 5, seed=seed)
        x = jnp.asarray(_rand_x(64, seed=seed))
        y_ref = m.to_dense() @ np.asarray(x)
        for fmt, kw in [("csr", {}), ("jds", {}), ("sell", dict(C=4))]:
            y = np.asarray(SpMVPlan.compile(F.convert(m, fmt, **kw))(x))
            np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


def test_plan_bsr_and_dia():
    d = block_sparse_dense(64, 256, (8, 128), 0.4, seed=1)
    mb = F.BSR.from_dense(d, (8, 128))
    x = jnp.asarray(_rand_x(256, seed=0))
    np.testing.assert_allclose(np.asarray(SpMVPlan.compile(mb)(x)),
                               d @ np.asarray(x), rtol=2e-4, atol=1e-4)
    hh = holstein_hubbard_surrogate(500, seed=2)
    dia = F.split_dia(hh).dia
    xd = jnp.asarray(_rand_x(500, seed=1))
    np.testing.assert_allclose(np.asarray(SpMVPlan.compile(dia)(xd)),
                               dia.to_dense() @ np.asarray(xd), rtol=1e-4, atol=1e-4)


# --- plan memoization + cached preprocessing --------------------------------

def test_plan_compile_is_memoized(hh_small):
    sell = F.convert(hh_small, "sell", C=8)
    p1 = SpMVPlan.compile(sell)
    p2 = SpMVPlan.compile(sell)
    assert p1 is p2
    p3 = SpMVPlan.compile(sell, backend="pallas")
    assert p3 is not p1


def test_plan_no_repreprocessing_across_calls(hh_small):
    """Compiling and repeatedly executing a plan performs each host
    preprocessing step exactly once."""
    m = holstein_hubbard_surrogate(400, seed=7)
    sell = F.SELL.from_csr(m, C=8)
    before = S.precompute_stats()
    p_csr = SpMVPlan.compile(m)
    p_sell = SpMVPlan.compile(sell)
    x = jnp.asarray(_rand_x(400))
    for _ in range(4):
        p_csr(x)
        p_sell(x)
        SpMVPlan.compile(m)  # re-compile hits the memo, not the builders
    after = S.precompute_stats()
    assert after["csr_row_ids"] - before["csr_row_ids"] == 1
    # the XLA SELL entry builds exactly one cached operand set — flat rids
    # when the dual-formulation predicate picks the flat stream, the padded
    # (nc, W, C) views otherwise
    stat = ("sell_flat_rids" if PM.sell_xla_uses_flat(sell)
            else "sell_padded_views")
    assert after[stat] - before[stat] == 1


def test_plan_report_fields(hh_small):
    plan = SpMVPlan.compile(F.convert(hh_small, "sell", C=8))
    r = plan.report
    assert r.format == "sell" and r.nnz == hh_small.nnz
    assert r.kernel in ("xla", "pallas", "pallas-interpret")
    assert r.balance_bytes_per_flop > 0 and r.predicted_gflops > 0
    assert r.bound in ("memory", "compute")


# --- model-driven Pallas autotuning ----------------------------------------

def test_select_pallas_blocks_fits_vmem():
    from repro.kernels.sell_spmv import vmem_bytes
    from repro.utils.hw import TPU_V5E
    blk = PM.select_pallas_blocks(1000, 20, 8, 100_000)
    assert 1000 % blk.chunk_block == 0
    assert blk.width_padded % blk.width_block == 0
    assert blk.fits_vmem
    claim = vmem_bytes(blk.chunk_block, blk.width_block, 8, 100_000)
    assert claim <= TPU_V5E.vmem_bytes / 2


def test_select_pallas_blocks_overflow_flagged():
    import dataclasses
    tiny = dataclasses.replace(PM.TPU_V5E, vmem_bytes=1024)
    blk = PM.select_pallas_blocks(1000, 20, 8, 1_000_000, chip=tiny)
    assert not blk.fits_vmem  # x alone blows the budget -> caller falls back


def test_plan_pallas_interpret_fallback(hh_small):
    """Off-TPU the pallas backend runs the kernel in interpret mode and
    stays correct (the compiled path flips on automatically on TPU)."""
    sell = F.convert(hh_small, "sell", C=8)
    plan = SpMVPlan.compile(sell, backend="pallas")
    expected = "pallas" if jax.default_backend() == "tpu" else "pallas-interpret"
    assert plan.report.kernel == expected
    assert plan.report.chunk_block is not None
    x = jnp.asarray(_rand_x(hh_small.shape[1]))
    np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(S.spmv(hh_small, x)),
                               rtol=2e-5, atol=2e-5)


def test_plan_all_formats_ranks(hh_small):
    plans = plan_all_formats(hh_small, formats=("csr", "sell", "hybrid"))
    assert set(plans) == {"csr", "sell", "hybrid"}
    best = min(plans, key=lambda k: plans[k].report.predicted_time_s)
    assert best in plans


# --- consumers --------------------------------------------------------------

def test_eigensolver_accepts_containers(hh_exact):
    from repro.core.eigensolver import ground_state_energy
    ev = np.linalg.eigvalsh(hh_exact.to_dense())
    e_plan = ground_state_energy(hh_exact, hh_exact.shape[0], m=60)
    assert e_plan == pytest.approx(ev[0], abs=5e-4)
    sell = F.SELL.from_csr(hh_exact, C=8)
    e_sell = ground_state_energy(SpMVPlan.compile(sell), hh_exact.shape[0], m=60)
    assert e_sell == pytest.approx(e_plan, abs=1e-5)


def test_sparse_operator_server(hh_small):
    from repro.serve.engine import SparseOperatorServer
    srv = SparseOperatorServer(backend="auto")
    rep = srv.register("hh", F.convert(hh_small, "sell", C=8))
    assert rep.format == "sell"
    x = jnp.asarray(_rand_x(hh_small.shape[1]))
    y = np.asarray(srv.spmv("hh", x))
    np.testing.assert_allclose(y, np.asarray(S.spmv(hh_small, x)), rtol=2e-5, atol=2e-5)
    X = jnp.asarray(_rand_x(hh_small.shape[1], k=3))
    Y = np.asarray(srv.spmm("hh", X))
    assert Y.shape == (hh_small.shape[0], 3)
    st = srv.stats()["hh"]
    assert st["calls"] == 4 and st["predicted_gflops"] > 0


def test_distributed_plan(hh_small):
    """Back-compat entry point delegates to the distributed plan layer:
    all three variants, with working SpMM executors."""
    from repro.core import distributed as D
    x = jnp.asarray(_rand_x(hh_small.shape[1]))
    X = jnp.asarray(_rand_x(hh_small.shape[1], k=4))
    y_ref = np.asarray(S.spmv(hh_small, x))
    Y_ref = np.asarray(S.spmm(hh_small, X))
    for strategy in ("allgather", "ring", "overlap"):
        plan = D.compile_distributed_plan(hh_small, strategy=strategy)
        assert plan.strategy == strategy  # alias of .variant
        assert plan.parts == len(jax.devices())
        assert plan.imbalance >= 1.0
        assert plan.slab_format in ("ell", "sell")
        np.testing.assert_allclose(np.asarray(plan(x)), y_ref, rtol=2e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(plan.spmm(X)), Y_ref, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", ["csr", "ell", "sell", "hybrid", "dia", "auto"])
def test_lowered_plan_does_not_grow_with_nnz(fmt):
    """A plan's program takes the matrix as arguments: doubling N (and so
    nnz) leaves the lowered StableHLO within 10% of its length."""
    from repro.core.planconfig import PlanConfig

    def hlo_len(n):
        m = holstein_hubbard_surrogate(n, seed=0)
        cfg = PlanConfig(format=fmt) if fmt == "auto" else PlanConfig()
        obj = m if fmt == "auto" else F.convert(m, fmt)
        plan = SpMVPlan.compile(obj, cfg)
        x = jnp.zeros(n, jnp.float32)
        return len(plan.kernel.lower(plan.operands, x).as_text())

    small, large = hlo_len(4_000), hlo_len(8_000)
    assert abs(large - small) <= 0.1 * small, (small, large)


@pytest.mark.parametrize("fmt", ["sell", "hybrid", "dia"])
def test_kernel_entry_does_not_grow_with_nnz(fmt):
    """``ops.make_kernel_spmv`` returns the jitted kernel with the matrix
    bound as its leading arguments (no outer jit closing over it again):
    doubling N leaves the lowered StableHLO within 10% of its length."""
    from repro.kernels import ops

    def hlo_len(n):
        m = F.convert(holstein_hubbard_surrogate(n, seed=0), fmt)
        f = ops.make_kernel_spmv(m)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
        np.testing.assert_allclose(np.asarray(f(x)), m.to_dense() @ np.asarray(x),
                                   rtol=2e-4, atol=1e-4)
        return len(f.func.lower(*f.args, x).as_text())

    small, large = hlo_len(4_000), hlo_len(8_000)
    assert abs(large - small) <= 0.1 * small, (small, large)


def test_compile_cache_dir_follows_the_environment(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR when set, else a fixed <repo>/.jax_cache."""
    from repro.utils import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert compile_cache.cache_dir() == "/elsewhere/jax"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").is_file()
