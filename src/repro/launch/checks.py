"""Logit and kernel checks for the served decode path.

- ``arena_row_logits`` vs ``reference_logits``: one freshly leased arena
  row, stepped by the engine's own compiled decode program (all
  ``max_slots`` rows), against a batch-1 ``decode_step`` on an empty
  cache fed the same token at cursor 0.
- ``pallas_vs_xla``: the ``decode_attention`` kernel on the served
  arena's first-layer K/V against the float32 oracle
  (``kernels/ref.py``), beside the XLA attention path; then one whole
  decode step with ``impl="pallas"`` against ``impl="xla"`` on the same
  params, arena, cursors and live-row bitmap.

Every comparison is ``max |got - ref| <= rtol * max |ref|`` over the
compared rows; logit comparisons also require the argmax to agree —
unless the reference's two largest logits lie closer than the
tolerance, where the argmax is not determined at the stated precision
and the chosen token's reference logit must lie within the tolerance
of the maximum. Why not equality, per comparison:

- ``LOGIT_RTOL`` (arena row vs batch-1 reference): the same math in
  another reduction order — XLA picks it by batch shape. f32 leaves
  ulp-level noise (1e-5 covers it with margin); bf16 rounds every
  activation to an 8-bit significand (eps 2^-7) between 40 layers, so
  a flipped rounding early moves the logits by a few eps (5e-2).
- ``ATTN_RTOL`` (one attention call vs the f32 oracle): the kernel and
  the XLA path both round their output to the cache dtype and their
  softmax weights to it before the PV matmul, so each is within about
  one eps of the oracle (2^-7 in bf16).
- ``STEP_RTOL`` (whole decode step, kernel vs XLA): the two round
  every attention output differently, in each of 40 layers, and a
  random-init bf16 stack amplifies such one-ulp differences (on a v5e
  the kernel-free batch-shape comparison above already moves granite's
  logits by 1.6% of their maximum). The band (25%) catches wiring
  faults — wrong head, row or cursor give O(1) differences — while the
  kernel's precision is held by ``ATTN_RTOL``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.kernels.ref import decode_attention_ref
from repro.models import model_for
from repro.models.attention import build_mask, dense_attention

LOGIT_RTOL = {"float32": 1e-5, "bfloat16": 5e-2}
ATTN_RTOL = {"float32": 1e-5, "bfloat16": 2.0**-7}
STEP_RTOL = {"float32": 1e-5, "bfloat16": 0.25}


def _max_err(got, ref, rtol: float) -> Dict[str, object]:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise ValueError(f"shapes differ: {got.shape} vs {ref.shape}")
    scale = float(np.abs(ref).max())
    tol = rtol * scale
    diff = float(np.abs(got - ref).max())
    return {"max_abs_diff": diff, "ref_max_abs": scale, "tol": tol,
            "ok": bool(diff <= tol)}


def compare_logits(got, ref, rtol: float) -> Dict[str, object]:
    """(rows, vocab) logits: max error within ``rtol`` and the argmax
    rule (module docstring)."""
    got = np.atleast_2d(np.asarray(got, np.float32))
    ref = np.atleast_2d(np.asarray(ref, np.float32))
    out = _max_err(got, ref, rtol)
    rows = np.arange(ref.shape[0])
    top_got = got.argmax(-1)
    agree = top_got == ref.argmax(-1)
    # A near-tie in the reference: the chosen token must still be one
    # the reference ranks within ``tol`` of its maximum.
    tie_ok = ref[rows, top_got] >= ref.max(-1) - out["tol"]
    out.update(argmax_agree=int(agree.sum()), rows=int(ref.shape[0]))
    out["ok"] = bool(out["ok"] and np.all(agree | tie_ok))
    return out


def reference_logits(engine, mid: str, seq: int, token: int) -> np.ndarray:
    """Batch-1 ``decode_step`` of ``token`` at cursor 0 on an empty
    cache, on ``engine``'s device with its params: (vocab,) logits."""
    model = engine.models[mid]
    with jax.default_device(engine.device):
        cache = model.init_cache(1, seq)
        tok = jnp.array([token], jnp.int32)
        cur = jnp.zeros((1,), jnp.int32)
    logits, _ = jax.jit(model.decode_step)(engine.params[mid], cache, tok, cur)
    return np.asarray(logits[0])


def arena_row_logits(engine, mid: str, seq: int, token: int) -> np.ndarray:
    """Lease one fresh arena row (cursor 0), step ``token`` through the
    engine's served decode program, free the row: (vocab,) logits."""
    (row,) = engine.alloc_slots(mid, seq, 1)
    try:
        live = engine.arena(mid, seq).live
        out = engine.dispatch(
            mid, (seq,), len(live), "decode", slots=live,
            payload={row: token}, step_rows=[row],
        ).wait()
        return np.asarray(out[row])
    finally:
        engine.free_slots(mid, seq, [row])


def pallas_vs_xla(
    engine, mid: str, seq: int, history: int = 3, seed: int = 0
) -> Dict[str, object]:
    """The Pallas decode kernel against the f32 oracle and the XLA path.

    Leases every arena row and steps ``history`` seeded tokens through
    the served program, so each row attends to real cached positions.
    Then, with the last row idle (the kernel's dead-row block skip on
    the path): one attention call on the first layer's K/V against the
    oracle (``ATTN_RTOL``), and one whole decode step with
    ``impl="pallas"`` against ``impl="xla"`` (``STEP_RTOL``), live rows
    only. Needs an arena with no leased rows; frees them afterwards.
    """
    cfg = engine.configs[mid]
    rng = np.random.default_rng(seed)
    m = engine.max_slots
    rows = engine.alloc_slots(mid, seq, m)
    try:
        for _ in range(history):
            tokens = rng.integers(0, cfg.vocab_size, size=m, dtype=np.int32)
            engine.dispatch(
                mid, (seq,), m, "decode", slots=rows, payload=tokens
            ).wait()
        arena = engine.arena(mid, seq)
        live = list(rows[:-1])
        tok = jax.device_put(
            rng.integers(0, cfg.vocab_size, size=m, dtype=np.int32),
            engine.device,
        )
        active = jax.device_put(np.isin(np.arange(m), live), engine.device)
        attention = _attention_vs_oracle(engine, cfg, arena, active, live, seed)
        logits = {}
        for impl in ("xla", "pallas"):
            model = model_for(dataclasses.replace(cfg, impl=impl))
            step = jax.jit(
                lambda p, c, t, cu, a, _m=model: _m.decode_step(
                    p, c, t, cu, active=a
                )[0]
            )
            logits[impl] = np.asarray(
                step(engine.params[mid], arena.cache, tok, arena.cur, active)
            )[live]
        step = compare_logits(
            logits["pallas"], logits["xla"], STEP_RTOL[cfg.param_dtype]
        )
        return {"attention": attention, "step": step}
    finally:
        engine.free_slots(mid, seq, rows)


def _attention_vs_oracle(engine, cfg, arena, active, live, seed):
    """The kernel (compiled on TPU, interpreted on the CPU) and the XLA
    attention path on the arena's first-layer K/V at the arena's
    cursors, each against the f32 oracle; ``ok`` judges the kernel."""
    layer = jax.tree.map(lambda x: x[0], arena.cache["super"][0])
    ck, cv, cur = layer["k"], layer["v"], arena.cur
    b, s, _kv, d = ck.shape
    q = jax.device_put(
        np.random.default_rng(seed).standard_normal((b, 1, cfg.n_heads, d)),
        engine.device,
    ).astype(ck.dtype)
    pos = jax.device_put(
        np.broadcast_to(np.arange(s, dtype=np.int32), (b, s)), engine.device
    )
    valid = pos <= cur[:, None]
    kernel = kernel_ops.decode_attention(q, ck, cv, cur, pos, valid, active)
    mask = build_mask(cur[:, None], pos, valid & active[:, None], True, None)
    xla = dense_attention(q, ck, cv, mask)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    # TPU matmuls default to one bf16 pass even on f32 operands.
    with jax.default_matmul_precision("highest"):
        oracle = decode_attention_ref(
            f32(q), f32(ck), f32(cv), cur, pos, valid, active
        )
    oracle = np.asarray(oracle)[live]
    out = _max_err(np.asarray(kernel)[live], oracle, ATTN_RTOL[cfg.param_dtype])
    out["xla_max_abs_diff"] = float(
        np.abs(np.asarray(xla, np.float32)[live] - oracle).max()
    )
    return out


def placed_on(trees: Sequence, device) -> bool:
    """Every array leaf of ``trees`` lives on exactly ``{device}``."""
    return all(
        leaf.devices() == {device}
        for tree in trees
        for leaf in jax.tree.leaves(tree)
    )
