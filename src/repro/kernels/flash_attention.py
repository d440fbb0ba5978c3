"""Pallas TPU flash attention — the LM stack's perf-critical leaf.

Causal GQA flash attention with explicit BlockSpec VMEM tiling:

- grid = (batch, kv_heads, q_blocks, kv_blocks); KV blocks iterate fastest
  so the output tile and the running (m, l) statistics live across the
  innermost dimension (same accumulation pattern as the sparse ELL kernels).
- queries are pre-reshaped to (B, Hkv, q_blocks·G·block_q, hd) with the G
  query groups of each block stacked row-wise, so one MXU tile is
  (G·block_q, hd) × (hd, block_k) against the UN-repeated K/V block — GQA
  comes for free with no KV repetition (the §Perf iteration-1 lesson,
  applied at kernel level).
- causal masking is positional; fully-masked KV blocks still execute (XLA
  grids are static) — the known ~2× FLOP overhead is the same one the
  roofline reports for the jnp paths.

Validated under interpret=True against models/attention's jnp oracle
(tests/test_flash_kernel.py) across shapes, head counts and group sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, seq_len: int, groups: int,
                  scale: float):
    kb = pl.program_id(3)
    nkb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q: (G·block_q, hd) — G query groups stacked row-wise
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]                      # (block_k, hd)
    v = v_ref[0, 0, :, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qb = pl.program_id(2)
    # rows are group-major: row = g·block_q + r  →  position = qb·block_q + r
    q_pos = qb * block_q + (jax.lax.broadcasted_iota(
        jnp.int32, (groups * block_q, block_k), 0) % block_q)
    kv_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (groups * block_q, block_k), 1)
    mask = (kv_pos <= q_pos) & (kv_pos < seq_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (G·block_q, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # fully-masked block: m_new stays NEG_INF and exp(0)=1 would leak —
    # re-apply the mask to the probabilities
    p = jnp.where(mask, p, 0.0)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kb == nkb - 1)
    def _finish():
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)
                             ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """Causal GQA flash attention.

    q: (B, S, H, hd); k, v: (B, S, Hkv, hd) with H = G·Hkv.
    Returns (B, S, H, hd). S is padded internally to the block sizes.
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Sp = -(-S // max(block_q, block_k)) * max(block_q, block_k)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    # (B, Hkv, G·S?, hd): group-major rows per q block:
    # row index = g * block_q + r within each (G·block_q) tile
    qg = q.reshape(B, Sp, Hkv, G, hd).transpose(0, 2, 3, 1, 4)  # B,K,G,S,hd
    nqb = Sp // block_q
    qg = qg.reshape(B, Hkv, G, nqb, block_q, hd).transpose(0, 1, 3, 2, 4, 5)
    qg = qg.reshape(B, Hkv, nqb * G * block_q, hd)
    kg = k.transpose(0, 2, 1, 3)               # (B, Hkv, Sp, hd)
    vg = v.transpose(0, 2, 1, 3)
    grid = (B, Hkv, nqb, Sp // block_k)
    gq = G * block_q

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, groups=G, scale=hd ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nqb * gq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),   # running max
            pltpu.VMEM((gq, 1), jnp.float32),   # running denom
            pltpu.VMEM((gq, hd), jnp.float32),  # output accum
        ],
        interpret=interpret,
    )(qg, kg, vg)
    # back to (B, S, H, hd)
    out = out.reshape(B, Hkv, nqb, G, block_q, hd).transpose(0, 1, 3, 2, 4, 5)
    out = out.reshape(B, Hkv, G, Sp, hd).transpose(0, 3, 1, 2, 4)
    out = out.reshape(B, Sp, H, hd)
    return out[:, :S]
