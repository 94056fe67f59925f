"""NN substrate + launch-layer units: chunked CE oracle, RoPE properties,
logical-axis translation, HLO collective parser, perlin determinism."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.nn.core import cross_entropy_chunked, rms_norm, rope
from repro.runtime.meshctx import logical_to_spec, use_mesh, constrain
from repro.launch.dryrun import collective_bytes, _shape_bytes
from repro.data import perlin_noise


def test_chunked_ce_matches_full():
    key = jax.random.PRNGKey(0)
    b, s, d, v = 2, 16, 8, 32
    h = jax.random.normal(key, (b, s, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v))
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)
    full = -jnp.take_along_axis(
        jax.nn.log_softmax(h @ w, -1), labels[..., None], -1).mean()
    for nc in (1, 2, 4, 8):
        got = cross_entropy_chunked(h, w, labels, n_chunks=nc)
        np.testing.assert_allclose(float(got), float(full), rtol=1e-5)


def test_chunked_ce_padding_labels():
    h = jnp.ones((1, 4, 8))
    w = jnp.zeros((8, 16))
    labels = jnp.array([[1, 2, -1, -1]])
    got = cross_entropy_chunked(h, w, labels, n_chunks=2)
    # uniform logits -> log(16); padded positions excluded
    np.testing.assert_allclose(float(got), np.log(16), rtol=1e-6)


def test_rms_norm_unit_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 10
    y = rms_norm(x, jnp.ones(64))
    rms = jnp.sqrt((y * y).mean(-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)


def test_rope_preserves_norm_and_relative_phase():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    y = rope(x, pos)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # dot(q_i, k_j) depends only on i - j
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 16))
    def dot_at(i, j):
        qi = rope(jnp.broadcast_to(q, (1, 1, 1, 16)),
                  jnp.full((1, 1), i))
        kj = rope(jnp.broadcast_to(k, (1, 1, 1, 16)),
                  jnp.full((1, 1), j))
        return float(jnp.sum(qi * kj))
    np.testing.assert_allclose(dot_at(5, 3), dot_at(7, 5), rtol=1e-4)


def test_logical_to_spec_drops_missing_axes():
    mesh1 = jax.make_mesh((1,), ("data",))
    spec = logical_to_spec(("dp", "tp", None), mesh1)
    assert spec == jax.sharding.PartitionSpec("data", None, None)
    mesh2 = jax.make_mesh((1, 1), ("data", "model"))
    spec = logical_to_spec(("dp", "sp", None), mesh2)
    assert spec == jax.sharding.PartitionSpec("data", "model", None)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "dp", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shape_bytes_parser():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[2,4]{1,0}") == 16
    assert _shape_bytes("(f32[8], s32[4])") == 32 + 16
    assert _shape_bytes("pred[16]") == 16


def test_collective_parser():
    hlo = """
  %ag = bf16[512,1024]{1,0} all-gather(bf16[32,1024] %x), dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(f32[256] %y), to_apply=%add
  %cp = f32[2,8]{1,0} collective-permute(f32[2,8] %z), source_target_pairs={{0,1}}
  %nothing = f32[4] add(f32[4] %a, f32[4] %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 512 * 1024 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["collective-permute"] == 2 * 8 * 4
    assert out["n_collectives"] == 3
    assert out["total"] == out["all-gather"] + out["all-reduce"] + \
        out["collective-permute"]


def test_perlin_shard_consistency():
    """Shards regenerating their own slab get bit-identical values — the
    weak-scaling data path never materialises the global grid."""
    full = perlin_noise((32, 16, 8), frequency=0.1, seed=0)
    slab = perlin_noise((8, 16, 8), frequency=0.1, seed=0, origin=(16, 0, 0))
    np.testing.assert_array_equal(full[16:24], slab)


@pytest.mark.parametrize("shape, seed, origin", [
    ((40, 16, 8), 0, None),        # two device slabs, the last ragged
    ((20, 17, 9), 3, None),
    ((12, 10, 8), 1, (5, 0, 0)),
    ((64, 64), 1, None),
])
def test_perlin_device_matches_host(shape, seed, origin):
    """The device generator evaluates the same formula: equal to the numpy
    field up to float32 rounding of its per-vertex sum."""
    from repro.data.perlin import perlin_noise_device
    want = perlin_noise(shape, frequency=0.1, seed=seed, origin=origin)
    got = np.asarray(perlin_noise_device(shape, 0.1, seed, origin))
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_perlin_statistics():
    f = perlin_noise((64, 64), frequency=0.1, seed=1)
    assert abs(float(f.mean())) < 0.1
    assert 0.05 < float(f.std()) < 1.0
