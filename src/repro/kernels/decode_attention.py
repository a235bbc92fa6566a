"""Decode (single-token) attention Pallas TPU kernel — flash-decoding.

One new token per sequence attends to its full (or ring) KV cache.
Schedule: grid (batch, kv_blocks); one grid step holds a kv block of ALL
kv heads and scores all H query heads against it at once (layout below).
Online-softmax state (m, l, acc) lives in VMEM scratch across kv blocks;
output written on the last block.

Layout (what the TPU compiler accepts): the last two dims of every block
must be multiples of (8, 128) or equal the array's. The (B, S, KV, D)
cache is therefore viewed as (B, S, KV * D) — lanes ``[h*D, (h+1)*D)``
of row ``s`` are slot ``s`` of kv head ``h`` — and blocked (1, bk,
KV * D). Queries go in block-diagonal: (H, KV * D) with query head
``(h, j)`` in kv head ``h``'s lanes and zeros elsewhere, so ONE
lane-dense matmul per block scores every head against its own kv head,
and the PV product's off-diagonal lanes are dropped by the wrapper.
Per-row ``cursor``/``active`` are scalar-prefetched into SMEM; per-slot
positions and validity are blocked as (1, 1, bk) tiles of (B, 1, S).
The (B, S, KV, D) -> (B, S, KV * D) view is a relayout copy of the
cache on TPU (different tiling), paid per call.

Masking is fully position-driven: the caller passes per-slot absolute
positions and a validity bitmap, so full caches, ring (sliding-window)
caches, and continuous-batching caches with per-sequence cursors all use
the same kernel. Fully-masked kv blocks are SKIPPED (``pl.when``), which
is bit-identical for any row with at least one live slot. On top of that
sits the slot-arena path: ``active`` is a per-row bitmap (the engine's
live-slot set — batch size as DATA, not shape), folded into every
block's skip test, so a dead arena row skips ALL its kv blocks. A row
with zero live slots outputs exact 0 (the mathematically sensible
"attended to nothing"), not the uniform mean-of-V an unskipped softmax
would give.
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
    cursor_ref,  # SMEM (B,) int32 — scalar prefetch
    active_ref,  # SMEM (B,) int32 (0/1) — live arena slot?
    q_ref,  # (1, H, KV * D) block-diagonal queries
    k_ref,  # (1, bk, KV * D)
    v_ref,
    pos_ref,  # (1, 1, bk) int32
    valid_ref,  # (1, 1, bk) int32 (0/1)
    o_ref,  # (1, H, KV * D)
    m_ref,  # (H, 1) f32
    l_ref,  # (H, 1) f32
    acc_ref,  # (H, KV * D) f32
    *,
    scale: float,
    window: Optional[int],
    n_kv_blocks: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cursor = cursor_ref[bi]
    pos = pos_ref[0]  # (1, bk)
    mask = jnp.logical_and(pos <= cursor, valid_ref[0] != 0)
    if window is not None:
        mask = jnp.logical_and(mask, pos > cursor - window)
    live = jnp.max(mask.astype(jnp.int32)) > 0

    # Skip fully-masked kv blocks: a masked block's contribution is
    # exactly zero (p underflows to 0, alpha = 1), so eliding the MXU
    # matmuls is bit-identical. This is what makes dead arena rows free —
    # ``active=0`` skips every kv block of the row — and a ring cache
    # skips its unwritten tail.
    @pl.when(jnp.logical_and(active_ref[bi] != 0, live))
    def _accumulate():
        q = q_ref[0]  # (H, KV * D)
        k = k_ref[0]  # (bk, KV * D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _write():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    cache_k: jax.Array,  # (B, S, KV, D)
    cache_v: jax.Array,
    cursor: jax.Array,  # (B,) int32
    kv_pos: jax.Array,  # (B, S) int32
    kv_valid: jax.Array,  # (B, S) bool
    active: Optional[jax.Array] = None,  # (B,) bool — None = all live
    *,
    window: Optional[int] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, one, h, d = q.shape
    if active is None:
        active = jnp.ones((b,), jnp.int32)
    s, kv = cache_k.shape[1], cache_k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    block_k = min(block_k, max(s, 8))
    nk = math.ceil(s / block_k)
    s_pad = nk * block_k
    kp = jnp.pad(cache_k, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    vp = jnp.pad(cache_v, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    pp = jnp.pad(kv_pos, ((0, 0), (0, s_pad - s)), constant_values=2**30)
    vv = jnp.pad(kv_valid.astype(jnp.int32), ((0, 0), (0, s_pad - s)))
    # Block-diagonal queries: row h*G + j carries query head (h, j) in
    # lanes [h*D, (h+1)*D) and zeros elsewhere, so one lane-dense
    # (H, KV*D) x (KV*D, bk) matmul gives every head's logits against
    # its own kv head (the zero lanes add exact zeros).
    eye = jnp.eye(kv, dtype=q.dtype)
    q_bd = (
        q.reshape(b, kv, g, 1, d) * eye[None, :, None, :, None]
    ).reshape(b, h, kv * d)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, n_kv_blocks=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, kv * d), lambda b_, k_, *_: (b_, 0, 0)),
            pl.BlockSpec((1, block_k, kv * d), lambda b_, k_, *_: (b_, k_, 0)),
            pl.BlockSpec((1, block_k, kv * d), lambda b_, k_, *_: (b_, k_, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, k_, *_: (b_, 0, k_)),
            pl.BlockSpec((1, 1, block_k), lambda b_, k_, *_: (b_, 0, k_)),
        ],
        out_specs=pl.BlockSpec((1, h, kv * d), lambda b_, k_, *_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, kv * d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, kv * d), q.dtype),
        interpret=interpret,
    )(
        cursor.astype(jnp.int32),
        active.astype(jnp.int32),
        q_bd,
        kp.reshape(b, s_pad, kv * d),
        vp.reshape(b, s_pad, kv * d),
        pp.astype(jnp.int32)[:, None, :],
        vv[:, None, :],
    )
    # Keep each query head's own kv-head lanes (the diagonal block); the
    # off-diagonal lanes mixed other heads' values and are discarded.
    out = (out.reshape(b, kv, g, kv, d) * eye[None, :, None, :, None]).sum(3)
    return out.reshape(b, 1, h, d)
