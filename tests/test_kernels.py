"""Per-kernel allclose tests vs the pure-jnp oracles (shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import gossip_cycle as gc
from repro.kernels import gossip_merge as gm
from repro.kernels import pegasos_update as pu
from repro.kernels import ref


@pytest.mark.parametrize("n,d", [(1, 8), (7, 57), (32, 128), (33, 300),
                                 (5, 1000)])
@pytest.mark.parametrize("lam", [0.1, 1e-3])
def test_pegasos_kernel_sweep(n, d, lam):
    key = jax.random.key(n * d)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (n, d), jnp.float32)
    x = jax.random.normal(ks[1], (n, d), jnp.float32)
    t = jax.random.randint(ks[2], (n,), 0, 100)
    y = jnp.sign(jax.random.normal(ks[3], (n,)))
    got_w, got_t = pu.pegasos_update(w, t, x, y, lam=lam, interpret=True)
    exp_w, exp_t = ref.pegasos_update_ref(w, t, x, y, lam)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(exp_w),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(exp_t))


@pytest.mark.parametrize("n,d", [(4, 16), (19, 257), (8, 512)])
def test_merge_update_kernel_sweep(n, d):
    key = jax.random.key(n + d)
    ks = jax.random.split(key, 6)
    w1 = jax.random.normal(ks[0], (n, d), jnp.float32)
    w2 = jax.random.normal(ks[1], (n, d), jnp.float32)
    x = jax.random.normal(ks[2], (n, d), jnp.float32)
    t1 = jax.random.randint(ks[3], (n,), 0, 40)
    t2 = jax.random.randint(ks[4], (n,), 0, 40)
    y = jnp.sign(jax.random.normal(ks[5], (n,)))
    got_w, got_t = gm.merge_update(w1, t1, w2, t2, x, y, lam=0.01,
                                   interpret=True)
    exp_w, exp_t = ref.merge_update_ref(w1, t1, w2, t2, x, y, 0.01)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(exp_w),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_t), np.asarray(exp_t))


@pytest.mark.parametrize("variant", ["mu", "um", "rw"])
@pytest.mark.parametrize("n,c,d,k", [(6, 3, 10, 2), (33, 10, 57, 4),
                                     (8, 5, 128, 1)])
def test_gossip_cycle_kernel_sweep(variant, n, c, d, k):
    """Fused deliver→merge→update→cache-write vs the apply_receives oracle."""
    from repro.core.cache import ModelCache, freshest
    from repro.core.learners import make_update
    from repro.core.simulation import apply_receives

    lam = 0.01
    key = jax.random.key(n * d + c)
    ks = jax.random.split(key, 8)
    last_w = jax.random.normal(ks[0], (n, d), jnp.float32)
    last_t = jax.random.randint(ks[1], (n,), 0, 30)
    cache = ModelCache(jax.random.normal(ks[2], (n, c, d), jnp.float32),
                       jax.random.randint(ks[3], (n, c), 0, 30),
                       jax.random.randint(ks[4], (n,), 1, 3 * c),
                       jnp.minimum(jax.random.randint(ks[4], (n,), 1, 3 * c), c))
    msg_w = jax.random.normal(ks[5], (k, n, d), jnp.float32)
    msg_t = jax.random.randint(ks[6], (k, n), 0, 30)
    valid = jax.random.bernoulli(ks[7], 0.7, (k, n))
    x = jax.random.normal(ks[0], (n, d), jnp.float32)
    y = jnp.sign(jax.random.normal(ks[1], (n,)) + 0.1)

    upd = make_update("pegasos", lam=lam)
    exp_lw, exp_lt, exp_cache, _, _ = apply_receives(
        last_w, last_t, cache, msg_w, msg_t, valid, x, y,
        variant=variant, update=upd)
    fresh_w, fresh_t = freshest(cache)
    got = gc.fused_receive_apply(
        last_w, last_t, fresh_w, fresh_t, cache.w, cache.t, cache.ptr,
        cache.count, msg_w, msg_t, valid.astype(jnp.int32), x, y,
        variant=variant, lam=lam, interpret=True)
    (got_lw, got_lt, got_fw, got_ft, got_cw, got_ct, got_ptr, got_cnt,
     _, _) = got
    exp_fw, exp_ft = freshest(exp_cache)
    np.testing.assert_allclose(np.asarray(got_fw), np.asarray(exp_fw),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_ft), np.asarray(exp_ft))
    np.testing.assert_allclose(np.asarray(got_lw), np.asarray(exp_lw),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_lt), np.asarray(exp_lt))
    np.testing.assert_allclose(np.asarray(got_cw), np.asarray(exp_cache.w),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_ct), np.asarray(exp_cache.t))
    np.testing.assert_array_equal(np.asarray(got_ptr), np.asarray(exp_cache.ptr))
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(exp_cache.count))


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 2, 2, 32),      # MHA
    (2, 128, 4, 2, 64),      # GQA 2:1
    (1, 256, 8, 1, 64),      # MQA
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, window):
    key = jax.random.key(B * S + H)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=causal, window=window,
                             blk_q=64, blk_k=64, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    key = jax.random.key(0)
    ks = jax.random.split(key, 3)
    B, S, H, KV, hd = 1, 128, 2, 1, 64
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(jnp.bfloat16)
    got = fa.flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64,
                             interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


def test_flash_attention_odd_head_dim_padding():
    key = jax.random.key(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 48), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 48), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 48), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                             interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-4)
