"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
sweeping shapes and dtypes as the deliverable requires."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.steepest_neighbor import steepest_neighbor
from repro.kernels.block_pathcompress import block_pathcompress
from repro.kernels.fused_local_phase import fused_local_phase
from repro.kernels.flash_attention import flash_attention
from repro.core.steepest import neighbor_offsets, grid_steepest

from oracles import GRID_SEED_CORPUS, ragged_grid_case

_ROOT = os.path.join(os.path.dirname(__file__), "..")


# --- steepest_neighbor -------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 8, 8), (16, 8, 8), (4, 16, 8),
                                   (32, 4, 4), (8, 5, 7)])
@pytest.mark.parametrize("conn", [6, 14])
def test_steepest_kernel_vs_ref(shape, conn):
    rng = np.random.default_rng(hash((shape, conn)) % 2**31)
    order = jnp.asarray(rng.permutation(int(np.prod(shape))).reshape(shape)
                        .astype(np.int32))
    got = steepest_neighbor(order, conn, block_x=4, interpret=True)
    want = ref.steepest_neighbor_ref(order, neighbor_offsets(3, conn))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_steepest_kernel_vs_core():
    """Kernel == the core library path used by DPC."""
    rng = np.random.default_rng(0)
    order = jnp.asarray(rng.permutation(8 * 8 * 8).reshape(8, 8, 8)
                        .astype(np.int32))
    got = steepest_neighbor(order, 6, block_x=2, interpret=True)
    want = grid_steepest(order, 6).reshape(order.shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("conn", [18, 26])
def test_steepest_kernel_full_neighborhoods(conn):
    """Digital-topology 18/26 neighborhoods (satellite of the fused-kernel
    PR): offset tables are symmetric and the kernel matches the oracle."""
    offs = neighbor_offsets(3, conn)
    assert len(offs) == conn
    assert all(tuple(-o for o in off) in offs for off in offs)
    rng = np.random.default_rng(conn)
    order = jnp.asarray(rng.permutation(8 * 5 * 7).reshape(8, 5, 7)
                        .astype(np.int32))
    got = steepest_neighbor(order, conn, block_x=4, interpret=True)
    want = ref.steepest_neighbor_ref(order, offs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_x", [1, 2, 8])
def test_steepest_kernel_blocking_invariance(block_x):
    rng = np.random.default_rng(1)
    order = jnp.asarray(rng.permutation(8 * 6 * 6).reshape(8, 6, 6)
                        .astype(np.int32))
    got = steepest_neighbor(order, 6, block_x=block_x, interpret=True)
    want = ref.steepest_neighbor_ref(order, neighbor_offsets(3, 6))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- fused_local_phase -------------------------------------------------------


def _fused_fixpoint_check(field, conn, mode, ptr):
    """The fused pointers must share their path_compress fixpoint with the
    plain unfused init — the contract that keeps final labels bit-identical."""
    from repro.core.pathcompress import path_compress
    from repro.core.steepest import grid_mask_argmax
    if mode == "manifold":
        d0 = grid_steepest(field, conn)
    else:
        d0 = grid_mask_argmax(field, conn)
    want, _ = path_compress(d0)
    got, _ = path_compress(ptr.ravel().astype(d0.dtype))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("conn", [6, 14, 18, 26])
@pytest.mark.parametrize("mode", ["manifold", "cc"])
def test_fused_kernel_vs_ref(conn, mode):
    """Kernel == bit-exact oracle on extents that exercise every halo read:
    a ragged last x tile, y tiles of 8 rows (the 8-row halo groups and, for
    conn >= 14, the x/y corner groups), a z extent that is not a lane
    multiple, plus the distributed ghost override on two axes."""
    shape = (7, 24, 5)
    rng = np.random.default_rng(conn * 7 + (mode == "cc"))
    if mode == "manifold":
        field = jnp.asarray(rng.permutation(int(np.prod(shape)))
                            .reshape(shape).astype(np.int32))
    else:
        field = jnp.asarray(rng.random(shape) < 0.6)
    got = fused_local_phase(field, conn, mode=mode, ghost_axes=(0, 1),
                            tile=(3, 8), interpret=True)
    want = ref.fused_local_phase_ref(field, conn, mode=mode,
                                     ghost_axes=(0, 1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", GRID_SEED_CORPUS)
def test_fused_kernel_corpus(seed):
    """Ragged seed corpus (prime extents): kernel == oracle AND the fused
    pointers reach the same fixpoint as grid_steepest/grid_mask_argmax +
    path_compress (2-D corpus cases are covered by the dispatch fallback
    tests — the kernel itself is 3-D only)."""
    shape, _, conn, mask_p = ragged_grid_case(seed)
    if len(shape) != 3:
        pytest.skip("fused kernel is 3-D only")
    rng = np.random.default_rng(seed)
    order = jnp.asarray(rng.permutation(int(np.prod(shape)))
                        .reshape(shape).astype(np.int32))
    mask = jnp.asarray(rng.random(shape) < mask_p)
    for mode, field in (("manifold", order), ("cc", mask)):
        got = fused_local_phase(field, conn, mode=mode, tile=(4, shape[1]),
                                interpret=True)
        want = ref.fused_local_phase_ref(field, conn, mode=mode)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        _fused_fixpoint_check(field, conn, mode, got)


@pytest.mark.parametrize("block_x", [1, 3, 8])
def test_fused_kernel_blocking_invariance(block_x):
    """Any tile gives the same pointers (block_x=3 on x=13 forces a ragged
    last tile; block_x=1 reads both x neighbors from the halo planes; y
    tiles of 8 and 16 rows on y=16 with the 14-stencil's corner reads)."""
    shape = (13, 16, 3)
    rng = np.random.default_rng(block_x)
    order = jnp.asarray(rng.permutation(int(np.prod(shape)))
                        .reshape(shape).astype(np.int32))
    want = ref.fused_local_phase_ref(order, 14, mode="manifold")
    for by in (8, 16):
        got = fused_local_phase(order, 14, mode="manifold",
                                tile=(block_x, by), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _fused_fixpoint_check(order, 14, "manifold", got)


def test_fused_dispatch_fallback_and_validation():
    """ops.fused_local_phase: jnp init for 2-D fields and unsupported
    connectivities, the same ghost override on the jnp and kernel paths,
    ValueError on a bad impl or mode."""
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    order2d = jnp.asarray(rng.permutation(30).reshape(5, 6).astype(np.int32))
    d = ops.fused_local_phase(order2d, connectivity=4, mode="manifold",
                              impl="kernel")
    want2d = grid_steepest(order2d, 4).reshape(order2d.shape)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want2d))
    order3d = jnp.asarray(rng.permutation(60).reshape(5, 4, 3)
                          .astype(np.int32))
    got = ops.fused_local_phase(order3d, 6, mode="manifold", impl="ref")
    want = grid_steepest(order3d, 6).reshape(order3d.shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    mask3d = jnp.asarray(rng.random((5, 4, 3)) < 0.5)
    for mode, field in (("manifold", order3d), ("cc", mask3d)):
        a = ops.fused_local_phase(field, 6, mode, ghost_axes=(0, 2),
                                  impl="ref")
        b = ops.fused_local_phase(field, 6, mode, ghost_axes=(0, 2),
                                  impl="kernel")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="impl"):
        ops.fused_local_phase(order3d, 6, impl="nope")
    with pytest.raises(ValueError, match="mode"):
        ops.fused_local_phase(order3d, 6, mode="nope")


def test_fused_kernel_rejects_2d_and_bad_conn():
    rng = np.random.default_rng(6)
    order2d = jnp.asarray(rng.permutation(30).reshape(5, 6).astype(np.int32))
    with pytest.raises(ValueError, match="3-D"):
        fused_local_phase(order2d, 4, interpret=True)
    order3d = jnp.asarray(rng.permutation(60).reshape(5, 4, 3)
                          .astype(np.int32))
    with pytest.raises(ValueError, match="connectivit"):
        fused_local_phase(order3d, 5, interpret=True)
    with pytest.raises(ValueError, match="tile"):
        fused_local_phase(order3d, 6, tile=(2, 2), interpret=True)


def test_steepest_kernel_rejects_2d_and_bad_conn():
    """Satellite: steepest_neighbor raises a clear ValueError instead of
    producing wrong halo geometry on inputs it cannot tile."""
    rng = np.random.default_rng(7)
    order2d = jnp.asarray(rng.permutation(30).reshape(5, 6).astype(np.int32))
    with pytest.raises(ValueError, match="3-D"):
        steepest_neighbor(order2d, 4, interpret=True)
    order3d = jnp.asarray(rng.permutation(60).reshape(5, 4, 3)
                          .astype(np.int32))
    with pytest.raises(ValueError, match="fallback"):
        steepest_neighbor(order3d, 5, interpret=True)


def test_fused_kernel_rejects_int64_without_x64():
    assert not jax.config.jax_enable_x64  # test-process invariant
    order = jnp.asarray(np.arange(24, dtype=np.int32).reshape(4, 3, 2))
    with pytest.raises(ValueError, match="x64"):
        fused_local_phase(order, 6, id_dtype=jnp.int64, interpret=True)


_FUSED_X64_WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_ENABLE_X64"] = "1"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.fused_local_phase import fused_local_phase
    from repro.kernels.ref import fused_local_phase_ref

    assert jax.config.jax_enable_x64
    rng = np.random.default_rng(11)
    shape = (7, 3, 4)
    order = jnp.asarray(rng.permutation(int(np.prod(shape)))
                        .reshape(shape).astype(np.int32))
    got = fused_local_phase(order, 14, mode="manifold", ghost_axes=(1,),
                            tile=(4, 3), interpret=True, id_dtype=jnp.int64)
    assert got.dtype == jnp.int64
    want = fused_local_phase_ref(order, 14, mode="manifold", ghost_axes=(1,),
                                 id_dtype=jnp.int64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("FUSED-X64-OK")
""")


def test_fused_kernel_int64_ids_under_x64():
    """Subprocess: the x64 flag is global, so the int64 pointer-id case must
    not leak into this (x64-off) test process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _FUSED_X64_WORKER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FUSED-X64-OK" in proc.stdout


def test_pure_entry_points_fused_parity():
    """descending/ascending manifold, ms_segmentation and CC grid labels are
    bit-identical between the default (jnp) and forced-kernel dispatch."""
    from repro.core.connected_components import connected_components_grid
    from repro.core.ms_segmentation import (ascending_manifold,
                                            descending_manifold,
                                            ms_segmentation)
    rng = np.random.default_rng(8)
    shape = (7, 4, 4)
    order = jnp.asarray(rng.permutation(int(np.prod(shape)))
                        .reshape(shape).astype(np.int32))
    mask = jnp.asarray(rng.random(shape) < 0.55)
    for fn in (descending_manifold, ascending_manifold):
        a, _ = fn(order, 6)
        b, _ = fn(order, 6, fused_impl="kernel")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s1 = ms_segmentation(order, 6)
    s2 = ms_segmentation(order, 6, fused_impl="kernel")
    np.testing.assert_array_equal(np.asarray(s1.segmentation),
                                  np.asarray(s2.segmentation))
    c1 = connected_components_grid(mask, 6)
    c2 = connected_components_grid(mask, 6, fused_impl="kernel")
    np.testing.assert_array_equal(np.asarray(c1.labels),
                                  np.asarray(c2.labels))


# --- block_pathcompress ------------------------------------------------------


@pytest.mark.parametrize("n,block", [(64, 16), (256, 64), (1024, 1024),
                                     (128, 32),
                                     # ragged last tile (pad-and-mask,
                                     # deviation (p) in DESIGN.md)
                                     (100, 32), (97, 64), (130, 128)])
@pytest.mark.parametrize("rounds", [1, 3, 6])
def test_block_pathcompress_vs_ref(n, block, rounds):
    rng = np.random.default_rng(n + rounds)
    d = np.arange(n)
    for v in range(n - 1):
        if rng.random() < 0.85:
            d[v] = rng.integers(v + 1, n)
    d[rng.random(n) < 0.05] = -1
    d = jnp.asarray(d, dtype=jnp.int32)
    got = block_pathcompress(d, rounds=rounds, block=block, interpret=True)
    # per-block oracle
    want = jnp.concatenate([
        ref.block_pathcompress_ref(d[i:i + block], rounds, base=i)
        for i in range(0, n, block)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_pathcompress_bucketed_recompile():
    """Satellite: request lengths snap to pow2 bucket capacities OUTSIDE the
    jit boundary, so one executable serves every length in a bucket (the
    serving engine replays ragged request streams; per-length recompiles
    were the cache-miss hot spot)."""
    from repro.kernels.block_pathcompress import _padded_call

    def chain(n, seed):
        rng = np.random.default_rng(seed)
        d = np.arange(n)
        for v in range(n - 1):
            if rng.random() < 0.8:
                d[v] = rng.integers(v + 1, n)
        return jnp.asarray(d, dtype=jnp.int32)

    _padded_call._clear_cache()
    for n in (100, 97, 80, 128):          # one bucket: cap 128
        d = chain(n, n)
        got = block_pathcompress(d, rounds=3, block=32, interpret=True)
        want = jnp.concatenate([
            ref.block_pathcompress_ref(d[i:i + 32], 3, base=i)
            for i in range(0, n, 32)])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert _padded_call._cache_size() == 1
    block_pathcompress(chain(130, 0), rounds=3, block=32, interpret=True)
    assert _padded_call._cache_size() == 2  # new bucket: cap 256


def test_block_pathcompress_then_global_converges():
    """Block rounds + global rounds give the same fixpoint as global-only
    (the correctness argument for the TPU schedule)."""
    from repro.core import path_compress
    rng = np.random.default_rng(3)
    n = 512
    d = np.arange(n)
    for v in range(n - 1):
        if rng.random() < 0.9:
            d[v] = rng.integers(v + 1, n)
    d = jnp.asarray(d, dtype=jnp.int32)
    pre = block_pathcompress(d, rounds=4, block=64, interpret=True)
    out_hybrid, it_hybrid = path_compress(pre)
    out_global, it_global = path_compress(d)
    np.testing.assert_array_equal(np.asarray(out_hybrid),
                                  np.asarray(out_global))


# --- flash attention ---------------------------------------------------------


@pytest.mark.parametrize("b,h,hkv,sq,sk,dh", [
    (1, 4, 4, 128, 128, 64),
    (2, 4, 2, 128, 256, 64),    # GQA group 2
    (1, 8, 1, 128, 128, 128),   # MQA
    (2, 2, 2, 256, 128, 32),    # cross (kv shorter)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_mha(b, h, hkv, sq, sk, dh, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, h, sq, dh), dtype)
    k = jax.random.normal(k2, (b, hkv, sk, dh), dtype)
    v = jax.random.normal(k3, (b, hkv, sk, dh), dtype)
    got = flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                          interpret=True)
    want = ref.mha_ref(q, k, v, causal=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 384), (256, 256)])
def test_flash_causal(sq, sk):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 4, sq, 64))
    k = jax.random.normal(k2, (1, 2, sk, 64))
    v = jax.random.normal(k3, (1, 2, sk, 64))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_ref_matches_mha_chunked():
    """The model-side chunked implementation == unfused reference."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (2, 8, 64, 32))
    k = jax.random.normal(k2, (2, 2, 192, 32))
    v = jax.random.normal(k3, (2, 2, 192, 32))
    got = ref.flash_attention_ref(q, k, v, causal=True, block_kv=64)
    want = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --- segment_bag (EmbeddingBag) ----------------------------------------------


@pytest.mark.parametrize("v,d,b,l,vb,bb", [
    (64, 8, 16, 5, 16, 8),
    (256, 32, 32, 16, 64, 32),
    (100, 16, 24, 4, 100, 24),   # single tile
    (512, 4, 8, 3, 128, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_bag_vs_embedding_bag(v, d, b, l, vb, bb, dtype):
    from repro.kernels.segment_bag import segment_bag
    from repro.models.bst import embedding_bag
    key = jax.random.PRNGKey(v + b)
    table = jax.random.normal(key, (v, d), dtype)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, l), -1, v)
    got = segment_bag(table, ids, vocab_block=vb, batch_block=bb,
                      interpret=True)
    # oracle in f32 (the kernel accumulates f32; bf16 ref sums reorder)
    want = embedding_bag(table.astype(jnp.float32), ids).astype(dtype)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_segment_bag_all_padding():
    from repro.kernels.segment_bag import segment_bag
    table = jnp.ones((32, 4))
    ids = jnp.full((8, 3), -1)
    got = segment_bag(table, ids, vocab_block=16, batch_block=8,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got), 0.0)
