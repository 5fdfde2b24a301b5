"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _qkv(b, s, h, kh, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32).astype(dtype)
    return q, k, v


def _ref(q, k, v, causal, window):
    b, s, h, hd = q.shape
    kh = k.shape[2]
    kk = jnp.repeat(k, h // kh, axis=2)
    vv = jnp.repeat(v, h // kh, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kb = kk.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vb = vv.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    o = ref.attention_ref(qb, kb, vb, causal, window)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("b,s,h,kh,hd", [
    (1, 128, 4, 4, 64),
    (2, 256, 4, 2, 64),
    (1, 200, 8, 2, 32),      # ragged seq (padding path)
    (2, 64, 2, 1, 128),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, s, h, kh, hd, causal):
    q, k, v = _qkv(b, s, h, kh, hd, jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal,
                            block_q=64, block_kv=96)
    oref = _ref(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 1000])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv(1, 160, 4, 2, 32, jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=64, block_kv=64)
    oref = _ref(q, k, v, True, window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q, k, v = _qkv(2, 128, 4, 2, 64, dtype)
    o = ops.flash_attention(q, k, v, causal=True)
    oref = _ref(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("shape", [(8, 128), (3, 100, 96), (2, 5, 7, 256),
                                   (1, 512)])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_rmsnorm_shapes_dtypes(shape, dtype, atol):
    x = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    sc = jax.random.normal(jax.random.fold_in(KEY, 1), shape[-1:],
                           jnp.float32)
    o = ops.rmsnorm(x, sc)
    oref = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), atol=atol,
                               rtol=atol)


def test_model_layer_pallas_path_matches_naive():
    """attn_impl='pallas' end-to-end through the model layer."""
    from repro.models import layers as L
    q, k, v = _qkv(2, 128, 4, 2, 32, jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(128), (2, 128))
    o_naive = L.attention(q, k, v, qpos, qpos,
                          opts=L.ModelOptions(attn_impl="naive"))
    o_pallas = L.attention(q, k, v, qpos, qpos,
                           opts=L.ModelOptions(attn_impl="pallas"))
    np.testing.assert_allclose(np.asarray(o_pallas), np.asarray(o_naive),
                               atol=2e-5, rtol=2e-5)


def test_combine_attention_partials_matches_full():
    """Online-softmax identity: attention over the full KV equals the
    exp-weighted combination of partials over disjoint KV shards — the
    math under ring attention (context parallelism)."""
    from repro.models import layers as L
    q, k, v = _qkv(2, 96, 4, 4, 32, jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(96), (2, 96))
    full = L.attention_naive(q, k, v, qpos, qpos, causal=True)
    parts = []
    for lo, hi in ((0, 32), (32, 64), (64, 96)):
        o, lse = L.attention_partial(q, k[:, lo:hi], v[:, lo:hi], qpos,
                                     qpos[:, lo:hi], causal=True,
                                     block_q=32, block_kv=32)
        parts.append((o, lse))
    combined = L.combine_attention_partials([p[0] for p in parts],
                                            [p[1] for p in parts])
    np.testing.assert_allclose(np.asarray(combined), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_single_ring():
    """ring_attention on a 1-element ring == plain flash attention."""
    from jax.sharding import PartitionSpec as P
    from repro.models import layers as L
    q, k, v = _qkv(1, 64, 4, 2, 32, jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(64), (1, 64))
    mesh = jax.make_mesh((1,), ("cp",))
    # realistic usage: sequence sharded over the ring axis
    f = jax.shard_map(
        lambda q, k, v, qp: L.ring_attention(q, k, v, qp, qp, "cp",
                                             block_q=32, block_kv=32),
        mesh=mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp"),
                  P(None, "cp")),
        out_specs=P(None, "cp"))
    out = f(q, k, v, qpos)
    ref = L.attention_flash_jnp(q, k, v, qpos, qpos, block_q=32,
                                block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
