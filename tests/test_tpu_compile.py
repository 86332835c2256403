"""Compile rehearsals for a described TPU v5e: what the chip's compiler
accepts, checked without the chip.

The v5e:2x2 topology is described inside a module fixture (never while a
module is imported), so only the worker that runs this file loads the TPU
compiler.  Nothing here runs on a device; each test lowers an executor of
the main path at the smoke run's sizes and compiles it for the described
chip, which refuses what Mosaic or XLA:TPU cannot lower.  One test fakes
the TPU platform to check that the probes refuse the kernels that do not
lower.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import formats as F
from repro.core import perfmodel as PM
from repro.core.matrices import (holstein_hubbard_surrogate, kronecker_graph,
                                 laplacian_3d, pagerank_transition)
from repro.kernels import registry as R

N_PAPER = 1_201_200   # chip_smoke.py phase (a)
GRID = 104            # chip_smoke.py phase (b)
N_KRON = 1 << 20      # the graph500_pagerank cell: a scale-20 Kronecker graph
KRON_ELEMS = 32_200_000   # its SELL pack's flat elements (nnz ~31.4M, x1.01 padding)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _compile(ck, x_shape, sharding):
    """Compile ``ck.kernel(operands, x)`` for the described chip."""
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32, sharding=sharding)
    return jax.jit(ck.kernel).lower(_shapes(ck.operands, sharding), x).compile()


def _hh_dia() -> F.DIA:
    """The DIA part of the phase-(a) hybrid: its 13 offsets, zero data."""
    m = holstein_hubbard_surrogate(20_000, seed=0)
    offsets = np.asarray(F.split_dia(m).dia.offsets)
    scale = N_PAPER / 20_000
    offsets = np.unique(np.round(offsets * scale)).astype(np.int32)
    return F.DIA(offsets, np.zeros((len(offsets), N_PAPER), np.float32),
                 (N_PAPER, N_PAPER))


def test_dia_pallas_compiles_for_v5e(one_chip):
    from repro.kernels import dia as KD
    dia = _hh_dia()
    ck = KD._build_dia_pallas(dia, R.KernelContext(), interpret=False)
    c = _compile(ck, (N_PAPER,), one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_dia_xla_executor_streams_without_a_gather(one_chip):
    """The DIA part of the phase-(a) hybrid on XLA at N = 1,201,200: shifted
    slices of a padded x, so the chip's program gathers nothing and
    its operands carry no (nd, n) index table."""
    from repro.kernels import dia as KD
    dia = _hh_dia()
    ck = KD._build_spmv(dia, R.KernelContext())
    nd = len(dia.offsets)
    assert not any(jnp.issubdtype(a.dtype, jnp.integer) and a.shape == (nd, N_PAPER)
                   for a in jax.tree.leaves(ck.operands))
    hlo = _compile(ck, (N_PAPER,), one_chip).as_text()
    assert not re.search(r"\bgather\(", hlo)


@pytest.mark.parametrize("k", [None, 8])
def test_matrix_free_pallas_compiles_for_v5e(one_chip, k):
    from repro.kernels import matrix_free as KMF
    op = F.convert(laplacian_3d(GRID, GRID, GRID, dtype=np.float32), "matrix_free")
    assert op.n_stored == 0  # every stencil diagonal is generated
    if k is None:
        ck = KMF._build_mf_pallas(op, R.KernelContext(), interpret=False)
        shape = (op.shape[1],)
    else:
        ck = KMF._build_mf_pallas_spmm(op, R.KernelContext(), interpret=False)
        shape = (op.shape[1], k)
    c = _compile(ck, shape, one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_paper_plan_executor_does_not_embed_the_matrix(one_chip, monkeypatch):
    """The phase-(a) executor (hybrid on XLA) at N = 1,201,200: its program
    takes the ~16.8M-nnz matrix as arguments, so its code stays small."""
    # the SELL part's XLA formulation is picked per runtime platform; take
    # the one the chip runs
    monkeypatch.setattr(PM, "sell_flat_overhead",
                        lambda family=None: PM.SELL_FLAT_OVERHEAD["tpu"])
    m = holstein_hubbard_surrogate(N_PAPER, seed=0)
    choice = PM.select_format(m)
    assert choice.format == "hybrid"
    h = F.convert(m, "hybrid", **choice.convert_kwargs)
    ck = R.build(h, "hybrid", "spmv", "xla")
    ma = _compile(ck, (N_PAPER,), one_chip).memory_analysis()
    assert ma.generated_code_size_in_bytes < 16 * 1024**2
    assert ma.argument_size_in_bytes > 100 * 1024**2  # the matrix is an argument


def test_pagerank_sell_flat_spmv_and_update_compile_for_v5e(one_chip, monkeypatch):
    """The graph500_pagerank cell's programs at its full size: the SELL
    XLA flat SpMV (a gather and a segment-sum over ~32.2M elements, the
    inverse-permutation gather over 2^20 rows) and the PageRank update.
    A Kronecker graph's longest row is thousands of times its mean, so the
    plan's operands must hold no padded (nc, W_max, C) view."""
    import functools

    from repro.core.eigensolver import _pagerank_update
    from repro.kernels import sell as KS
    monkeypatch.setattr(PM, "sell_flat_overhead",
                        lambda family=None: PM.SELL_FLAT_OVERHEAD["tpu"])
    P = pagerank_transition(kronecker_graph(12, seed=0))
    choice = PM.select_format(P)
    assert choice.format == "sell"
    s = F.convert(P, "sell", **choice.convert_kwargs)
    assert PM.sell_xla_uses_flat(s)
    ck = R.build(s, "sell", "spmv", "xla")
    leaves = jax.tree.leaves(ck.operands)
    assert [a.ndim for a in leaves] == [1, 1, 1, 1]     # col, val, row ids, inverse perm
    elems, n = len(s.val), P.shape[0]
    assert [a.shape[0] for a in leaves] == [elems, elems, elems, n]

    full = [jax.ShapeDtypeStruct((KRON_ELEMS if a.shape[0] == elems else N_KRON,), a.dtype,
                                 sharding=one_chip) for a in leaves]
    ops = jax.tree.unflatten(jax.tree.structure(ck.operands), full)
    kernel = functools.partial(KS._flat_spmv_kernel, n_rows=N_KRON, n_segments=N_KRON, C=s.C)
    x = jax.ShapeDtypeStruct((N_KRON,), jnp.float32, sharding=one_chip)
    ma = jax.jit(kernel).lower(ops, x).compile().memory_analysis()
    assert ma.argument_size_in_bytes >= 3 * 4 * KRON_ELEMS    # the matrix is an argument
    update = _pagerank_update.lower(x, x, x, damping=0.85).compile()
    assert update.memory_analysis().output_size_in_bytes >= 4 * N_KRON


def test_overlap_executor_compiles_on_2x2(topo):
    from repro.core import distributed_plan as DP
    n = 200_000
    m = holstein_hubbard_surrogate(n, seed=0)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    blocks = DP.pack_shard_slabs(m, 4, pack="sell", local_cols=True)
    run = DP._make_executor(blocks, mesh, "data", "overlap", multi=False)
    rid = blocks.rid
    slabs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(
        mesh, P("data", *([None] * (a.ndim - 1))))) for a in (blocks.col, blocks.val, rid)]
    rep = NamedSharding(mesh, P())
    inv = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep)
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rep)
    c = run.lower((*slabs, inv), x).compile()
    assert "collective-permute" in c.as_text()
    per_device = c.memory_analysis().argument_size_in_bytes
    slab_bytes = sum(a.nbytes for a in (blocks.col, blocks.val, rid))
    assert per_device < slab_bytes / 2  # each chip holds its quarter


def test_probes_refuse_what_does_not_lower_on_tpu(monkeypatch, hh_small):
    monkeypatch.setattr(R, "on_tpu", lambda: True)
    ctx = R.KernelContext()
    sell = F.SELL.from_csr(hh_small, C=8)
    hyb = F.split_dia(hh_small)
    refused = [("sell", "spmv", "pallas", sell), ("sell", "spmm", "pallas", sell),
               ("csr", "spmv", "pallas", hh_small),
               ("hybrid", "spmv", "pallas", hyb)]
    for fmt, op, be, obj in refused:
        cap = R.get(fmt, op, be).probe(obj, ctx)
        assert not cap.ok and "gather" in cap.reason, (fmt, op, be, cap)
    for e in R.entries():
        if e.backend in R.HOST_ONLY_BACKENDS:
            cap = e.probe(None, ctx)
            assert not cap.ok and cap.reason, e.key
    for fmt, obj in (("dia", hyb.dia), ("matrix_free",
                                         F.convert(hh_small, "matrix_free"))):
        assert R.get(fmt, "spmv", "pallas").probe(obj, ctx).ok, fmt
    from repro.serve.resilience import degradation_ladder
    assert degradation_ladder("hybrid", "xla", hyb) == []


@pytest.mark.parametrize("block_rows,fits", [(1984, True), (2048, False)])
def test_bell_smem_probe_matches_the_compiler(one_chip, block_rows, fits):
    """The BELL probe refuses exactly the block tables SMEM cannot hold
    (Mosaic pads the table's minor dimension to 128 words)."""
    from repro.kernels import bsr as KB
    nbpp, bm, bk = 4, 8, 128
    nb = block_rows * nbpp
    m = F.BSR(np.arange(0, nb + 1, nbpp, dtype=np.int32),
              np.tile(np.arange(nbpp, dtype=np.int32), block_rows),
              np.zeros((nb, bm, bk), np.float32), (block_rows * bm, 64 * bk),
              (bm, bk))
    cap = KB._probe_bell(m, R.KernelContext())
    assert cap.ok == fits, cap
    ck = KB._build_bell_spmm(m, R.KernelContext(), interpret=False)
    if fits:
        _compile(ck, (m.shape[1], 8), one_chip)
    else:
        with pytest.raises(Exception, match="smem"):
            _compile(ck, (m.shape[1], 8), one_chip)
