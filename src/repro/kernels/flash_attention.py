"""Flash attention Pallas TPU kernel (prefill/training path).

Schedule: grid (batch, heads, q_blocks, kv_blocks) — the kv axis is
innermost and TPU grids execute sequentially, so the online-softmax
running state (m, l, acc) lives in VMEM scratch and carries across kv
iterations; the output tile is written once on the last kv block.

Layout: the kernel reads head-major (B, H, S, D) views, so every block's
last two dims are (rows, D) — a multiple of 8 by the full head dim, the
shape the TPU compiler accepts. The wrapper transposes q/k/v in and the
output back; (B, S, H, D) blocks of one head would end in (1, D), which
the compiler refuses.

VMEM working set per grid step (f32):
    q tile (bq, D) + k/v tiles (bk, D) + logits (bq, bk) + acc (bq, D)
With bq = bk = 128, D <= 256 that is well under 1 MiB — far inside the
~16 MiB VMEM budget; block sizes are multiples of the 128-lane MXU tiling.

GQA is handled in the index map: the kv-head index is ``h // group``, so
K/V tiles are fetched once per kv head without materializing the
expanded (B, S, H, D) tensors the XLA fallback would need.

Causal and sliding-window masks are applied from block-relative iota
positions; fully-masked (q_block, kv_block) pairs are skipped via
``pl.when`` (block-sparse schedule — the same trick that makes causal
flash ~2x over the dense loop on TPU).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    seq_len: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k
    run = k_lo < seq_len  # skip fully padded kv blocks
    if causal:
        run = jnp.logical_and(run, k_lo <= q_lo + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_lo + block_k - 1 > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    kv = k.shape[2]
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(s, 8))
    nq = math.ceil(s / block_q)
    nk = math.ceil(s / block_k)
    s_pad_q = nq * block_q
    s_pad_k = nk * block_k
    # Head-major views: (B, H, S_pad, D) / (B, KV, S_pad, D).
    qp = jnp.pad(q, ((0, 0), (0, s_pad_q - s), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kp = jnp.pad(k, ((0, 0), (0, s_pad_k - s), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vp = jnp.pad(v, ((0, 0), (0, s_pad_k - s), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        seq_len=s,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, q_, k_: (b_, h_ // group, k_, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h_, q_, k_: (b_, h_ // group, k_, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out.transpose(0, 2, 1, 3)[:, :s]
