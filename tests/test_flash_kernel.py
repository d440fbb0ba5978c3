"""Pallas flash-attention kernel vs the jnp attention oracle (causal GQA),
swept over shapes, head/group counts, block sizes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention


def oracle(q, k, v):
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) / hd ** 0.5
    mask = np.tril(np.ones((S, S), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, -1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return o.reshape(B, S, H, hd)


CASES = [
    # B, S, H, Hkv, hd, bq, bk
    (2, 256, 4, 2, 32, 128, 128),
    (1, 200, 8, 8, 16, 64, 128),       # MHA + ragged S (padding path)
    (2, 384, 6, 2, 64, 128, 64),       # G=3, uneven blocks
    (1, 128, 16, 2, 32, 64, 64),       # G=8 (starcoder2-like ratio)
]


@pytest.mark.parametrize("case", CASES, ids=[f"S{c[1]}H{c[2]}k{c[3]}"
                                             for c in CASES])
def test_flash_matches_oracle_f32(case):
    B, S, H, Hkv, hd, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case)), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    got = flash_attention(q, k, v, block_q=bq, block_k=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    B, S, H, Hkv, hd = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.bfloat16)
    got = flash_attention(q, k, v, interpret=True)
    exp = oracle(q.astype(jnp.float32), k.astype(jnp.float32),
                 v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(exp),
                               atol=3e-2, rtol=3e-2)


def test_flash_first_token_and_padding_rows():
    """Row 0 attends only to itself; padded rows don't contaminate."""
    B, S, H, Hkv, hd = 1, 100, 2, 1, 16   # S pads to 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got[0, 0, 0]),
                               np.asarray(v[0, 0, 0]), atol=1e-5, rtol=1e-5)


def test_flash_variant_in_model():
    """The kernel is reachable as a model attention variant and agrees with
    the dense path end-to-end."""
    from repro.configs.base import ArchConfig
    from repro.models.model import LM
    cfg = ArchConfig(name="fl", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     head_dim=16, remat=False, dtype="float32")
    lm = LM(cfg)
    params = lm.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 128)
    dense_logits, _ = lm.apply(params, tokens, variant="dense")
    flash_logits, _ = lm.apply(params, tokens, variant="flash")
    np.testing.assert_allclose(np.asarray(flash_logits),
                               np.asarray(dense_logits), atol=1e-3,
                               rtol=1e-3)
