"""Pallas kernels (interpret mode) vs ref.py oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.core.matrices import block_sparse_dense, holstein_hubbard_surrogate, random_sparse
from repro.kernels import ops, ref as R
from repro.kernels.bsr_spmm import bell_spmm_arrays, bsr_to_bell
from repro.kernels.dia_spmv import dia_spmv
from repro.kernels.gather_bench import gather_scp, stream_triad, traffic_model
from repro.kernels.moe_gemm import grouped_gemm, plan_groups
from repro.kernels.sell_spmv import sell_spmv_arrays, vmem_bytes


# --- SELL ---------------------------------------------------------------

@pytest.mark.parametrize("C", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cb,wb", [(1, None), (4, None), (4, 2)])
def test_sell_kernel_sweep(C, dtype, cb, wb):
    m = random_sparse(64, 80, 6, seed=C)
    sell = F.SELL.from_csr(m, C=C)
    col3, val3, _ = sell.padded_views(pad_width_to=(wb or 1))
    col3 = jnp.asarray(col3)
    val3 = jnp.asarray(val3).astype(dtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(80), dtype)
    nc = col3.shape[0]
    cb_eff = cb if nc % cb == 0 else 1
    out = sell_spmv_arrays(col3, val3, x, chunk_block=cb_eff,
                           width_block=wb, interpret=True)
    ref = R.sell_spmv_ref(col3, val3, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_sell_kernel_end_to_end(hh_small):
    f_pallas = ops.make_sell_spmv(F.SELL.from_csr(hh_small, C=8), backend="pallas")
    f_ref = ops.make_sell_spmv(F.SELL.from_csr(hh_small, C=8), backend="ref")
    x = jnp.asarray(np.random.default_rng(1).standard_normal(hh_small.shape[1]).astype(np.float32))
    np.testing.assert_allclose(np.asarray(f_pallas(x)), np.asarray(f_ref(x)),
                               rtol=1e-5, atol=1e-5)


def test_sell_vmem_budget():
    # default tiling must fit a v5e VMEM with the paper's matrix dimension
    from repro.utils.hw import TPU_V5E
    assert vmem_bytes(8, 64, 128, 1_201_200) < TPU_V5E.vmem_bytes


# --- BSR / BELL ----------------------------------------------------------

@pytest.mark.parametrize("block", [(8, 128), (16, 128), (8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bell_spmm_sweep(block, dtype):
    bm, bk = block
    d = block_sparse_dense(bm * 6, bk * 4, block, 0.5, seed=3).astype(np.float32)
    m = F.BSR.from_dense(d, block)
    bcols, slab = bsr_to_bell(m)
    X = np.random.default_rng(0).standard_normal((d.shape[1], 32)).astype(np.float32)
    out = bell_spmm_arrays(jnp.asarray(bcols), jnp.asarray(slab).astype(dtype),
                           jnp.asarray(X).astype(dtype), interpret=True)
    ref = R.bell_spmm_ref(jnp.asarray(bcols), jnp.asarray(slab).astype(dtype),
                          jnp.asarray(X).astype(dtype))
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_bsr_vs_dense(hh_small):
    d = block_sparse_dense(128, 256, (8, 128), 0.3, seed=9)
    m = F.BSR.from_dense(d, (8, 128))
    f = ops.make_bsr_spmm(m, backend="pallas")
    X = jnp.asarray(np.random.default_rng(2).standard_normal((256, 8)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(f(X)), d @ np.asarray(X), rtol=2e-4, atol=1e-3)


# --- DIA ------------------------------------------------------------------

#: three 4096-row tiles, the last one partial: every test below runs the
#: kernel at program_id > 0 and reads windows across tile edges
N_MULTI_TILE = 2 * 4096 + 300


@pytest.mark.parametrize("tile", [4096, 8192])  # multiples of TILE_QUANTUM
def test_dia_kernel(tile):
    n = N_MULTI_TILE
    m = holstein_hubbard_surrogate(n, seed=2)
    hyb = F.split_dia(m)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(n).astype(np.float32))
    y = np.asarray(dia_spmv(hyb.dia, x, tile=tile, interpret=True))
    y_ref = hyb.dia.to_dense() @ np.asarray(x)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


def test_dia_negative_offsets():
    # offsets on both sides, lane-aligned and not, and past a whole tile:
    # the shifted windows start in the previous/next tile and straddle
    # 128-lane rows (the next-row select of shifted_window)
    offsets = np.asarray([-4200, -300, -129, -3, 0, 5, 128, 131, 4097], np.int32)
    n = N_MULTI_TILE
    data = np.random.default_rng(0).standard_normal((len(offsets), n)).astype(np.float32)
    # zero out-of-range slots as the format requires
    for k, off in enumerate(offsets):
        if off < 0:
            data[k, : -off] = 0.0   # row i reads x[i+off]; i < -off is out of range
        elif off > 0:
            data[k, n - off :] = 0.0
    dia = F.DIA(offsets, data, (n, n))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(n).astype(np.float32))
    y_ref = dia.to_dense() @ np.asarray(x)
    for tile in (4096, 8192):
        y = np.asarray(dia_spmv(dia, x, tile=tile, interpret=True))
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4,
                                   err_msg=f"tile={tile}")


def _dia(shape, offsets, seed=0):
    """A DIA container with random values, zero where a diagonal runs off
    the matrix (the format's contract)."""
    n, ncols = shape
    data = np.random.default_rng(seed).standard_normal((len(offsets), n)).astype(np.float32)
    i = np.arange(n)
    for k, off in enumerate(offsets):
        data[k, (i + off < 0) | (i + off >= ncols)] = 0.0
    return F.DIA(np.asarray(offsets, np.int32), data, shape)


@pytest.mark.parametrize("shape,offsets,value_dtype", [
    ((300, 300), [-40, -7, -1], None),               # negative only
    ((300, 300), [0, 3, 128, 299], None),            # positive only
    ((300, 200), [-5, 0, 17, 200, 260], None),       # offsets at and past ncols
    ((200, 300), [-250, -200, -3, 0, 129, 299], None),  # wide; wholly outside
    ((300, 200), [-299, -1, 0, 5], None),            # tall
    ((300, 300), [], None),                          # empty
    ((300, 300), [-9, 0, 4, 130], "int8"),           # per-diagonal scale
    ((300, 280), list(range(-150, 290, 4)), None),   # 110 diagonals
    ((300, 300), list(range(-200, 200, 5)), "int8"),  # 80 diagonals, scaled
])
def test_dia_xla_shifted_slices_match_dense(shape, offsets, value_dtype):
    """The XLA DIA executors (shifted slices of a zero-padded x)
    against the dense product, SpMV and SpMM, jitted as a plan runs them."""
    from repro.kernels import registry as REG
    m = _dia(shape, offsets)
    if value_dtype is not None:
        m = F.with_value_dtype(m, value_dtype)
        assert m.scale is not None
    dense = np.asarray(F.dequantize(m).to_dense(), np.float64)
    rng = np.random.default_rng(1)
    x = rng.standard_normal(shape[1]).astype(np.float32)
    X = rng.standard_normal((shape[1], 3)).astype(np.float32)
    ck = REG.build(m, "dia", "spmv", "xla")
    ckm = REG.build(m, "dia", "spmm", "xla")
    # the stored values (and scales) are the only operands of the values'
    # size: no (nd, n) index table
    for ops in (ck.operands, ckm.operands):
        leaves = jax.tree.leaves(ops)
        assert leaves[0].shape == m.data.shape
        assert all(a.size <= 2 * len(offsets) for a in leaves[1:])
    y = np.asarray(jax.jit(ck.kernel)(ck.operands, x))
    Y = np.asarray(jax.jit(ckm.kernel)(ckm.operands, X))
    assert y.shape == (shape[0],) and Y.shape == (shape[0], 3)
    np.testing.assert_allclose(y, dense @ x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Y, dense @ X, rtol=1e-5, atol=1e-5)


def test_hybrid_xla_plan_matches_its_loop_reference(hh_small):
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig
    from repro.kernels import registry as REG
    p = SpMVPlan.compile(hh_small, PlanConfig(format="hybrid", backend="xla"))
    assert p.report.format == "hybrid" and p.report.kernel == "xla"
    x = jnp.asarray(np.random.default_rng(3).standard_normal(hh_small.shape[1]).astype(np.float32))
    ref = REG.build(p.matrix, "hybrid", "spmv", "loop_reference").fn(x)
    np.testing.assert_allclose(np.asarray(p(x)), np.asarray(ref), rtol=1e-5, atol=1e-5)


# --- grouped GEMM ----------------------------------------------------------

@pytest.mark.parametrize("bt", [8, 32])
@pytest.mark.parametrize("E", [2, 5])
def test_grouped_gemm(bt, E):
    T, D, Fd = 70, 48, 40
    rng = np.random.default_rng(bt + E)
    X = jnp.asarray(rng.standard_normal((T, D)).astype(np.float32))
    W = jnp.asarray(rng.standard_normal((E, D, Fd)).astype(np.float32))
    eot = rng.integers(0, E, T)
    Y = np.asarray(grouped_gemm(X, eot, W, bt=bt, interpret=True))
    Y_ref = np.stack([np.asarray(X[t]) @ np.asarray(W[eot[t]]) for t in range(T)])
    np.testing.assert_allclose(Y, Y_ref, rtol=1e-4, atol=1e-3)


def test_plan_groups_invariants():
    eot = np.asarray([2, 0, 1, 1, 2, 2, 0])
    order, inv, tile_expert, T_pad = plan_groups(eot, 3, bt=4)
    assert T_pad % 4 == 0
    # every token lands in a tile of its own expert
    for t, dest in enumerate(inv):
        assert tile_expert[dest // 4] == eot[t]


# --- microbench kernels ------------------------------------------------------

def test_gather_bench_kernels():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    np.testing.assert_allclose(np.asarray(stream_triad(a, b, c, interpret=True)),
                               np.asarray(R.stream_triad_ref(a, b, c)),
                               rtol=1e-5, atol=1e-6)  # fma reassociation
    idx = jnp.asarray(rng.integers(0, 4096, 4096).astype(np.int32))
    out = np.asarray(gather_scp(a, idx, b, interpret=True))
    np.testing.assert_allclose(out, np.asarray(a) * np.asarray(b)[np.asarray(idx)], rtol=1e-6)
    tm = traffic_model(4096, 4)
    assert tm["stream_triad"] > tm["gather_scp"]
