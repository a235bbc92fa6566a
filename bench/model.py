"""The benchmark's own side of the model: sizes, weights, reference, costs.

Nothing here imports the program. ``Shape`` reads a configuration file
under ``bench/configs/``; ``make_weights`` draws the weights from the
run's seed on the device in one jitted call, in the tree layout the
program's transformer takes (``embed``, one stacked ``super`` block,
``final_norm``; see ``weight_layout``); ``reference_logits`` is the
plain float32 forward pass the served outputs are judged against, and
with ``quant=True`` the same pass with every matmul operand rounded to
float8 (e4m3), the control that has to fail; the ``*_flops`` and
``*_bytes`` functions count what a step needs from its shapes alone.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, c: Dict) -> "Shape":
        if c.get("hidden_act") != "silu" or not c.get("tie_word_embeddings"):
            raise ValueError("the reference implements tied SwiGLU blocks only")
        if c.get("partial_rotary_factor", 1.0) != 1.0:
            raise ValueError("the reference rotates whole heads only")
        return cls(
            layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            heads=int(c["num_attention_heads"]),
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            d_ff=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=str(c.get("torch_dtype", "bfloat16")),
        )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def weight_layout(s: Shape) -> Dict:
    """(shape, std) per leaf. Matrices are fan-in scaled, so every
    layer's output has unit scale; norm gains are 1 + N(0, 0.1²), so a
    path that skipped them would show; the tied embedding has std
    1/sqrt(d_model), which gives logits of unit scale."""
    L, D, H, K, E, F = (s.layers, s.d_model, s.heads, s.kv_heads,
                        s.head_dim, s.d_ff)
    return {
        "embed": ((s.vocab, D), 1.0 / math.sqrt(D)),
        "super": [{
            "norm1": {"scale": ((L, D), 0.1)},
            "mixer": {
                "wq": ((L, D, H, E), 1.0 / math.sqrt(D)),
                "wk": ((L, D, K, E), 1.0 / math.sqrt(D)),
                "wv": ((L, D, K, E), 1.0 / math.sqrt(D)),
                "wo": ((L, H, E, D), 1.0 / math.sqrt(H * E)),
            },
            "norm2": {"scale": ((L, D), 0.1)},
            "ffn": {
                "gate": ((L, D, F), 1.0 / math.sqrt(D)),
                "up": ((L, D, F), 1.0 / math.sqrt(D)),
                "down": ((L, F, D), 1.0 / math.sqrt(F)),
            },
        }],
        "tail": [],
        "final_norm": {"scale": ((D,), 0.1)},
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key_data(seed: int) -> np.ndarray:
    """Two uint32 words of a threefry key from any whole-number seed
    (seeds above 2**32 included)."""
    return np.random.SeedSequence(int(seed) % 2**64).generate_state(2)


def _weights_fn(s: Shape):
    layout = weight_layout(s)
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    dtype = jnp.dtype(s.dtype)

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        out = [
            (std * jax.random.normal(jax.random.fold_in(key, i), shape))
            .astype(dtype)
            for i, (shape, std) in enumerate(leaves)
        ]
        return jax.tree.unflatten(treedef, out)

    return make


def abstract_weights(s: Shape):
    """The weight tree's shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(_weights_fn(s), jax.ShapeDtypeStruct((2,), jnp.uint32))


def make_weights(s: Shape, seed: int, device) -> Dict:
    """All weights from ``seed``, on ``device``, in one jitted call."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(_weights_fn(s), out_shardings=sharding)
    return fn(jnp.asarray(seed_key_data(seed), jnp.uint32))


# ---------------------------------------------------------------------------
# Plain reference (float32, highest precision) and its float8 control
# ---------------------------------------------------------------------------


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the contraction axis), and back to float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(eq: str, a, b, quant: bool, a_axis: int, b_axis: int):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half RoPE over whole heads: x (B, S, N, E), pos (S,)."""
    e = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e))
    ang = pos.astype(jnp.float32)[:, None] * inv  # (S, E/2)
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shape, quant: bool, x, blk, l):
    """One decoder block on x (B, S, D) float32, causal over S."""
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, False), blk)
    b, n, _ = x.shape
    g = s.heads // s.kv_heads
    pos = jnp.arange(n)
    h = _rms(x, p["norm1"]["scale"], s.norm_eps)
    q = _dot("bsd,dhe->bshe", h, p["mixer"]["wq"], quant, -1, 0)
    k = _dot("bsd,dhe->bshe", h, p["mixer"]["wk"], quant, -1, 0)
    v = _dot("bsd,dhe->bshe", h, p["mixer"]["wv"], quant, -1, 0)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    qg = q.reshape(b, n, s.kv_heads, g, s.head_dim)
    sc = _dot("bqkge,bske->bkgqs", qg, k, quant, -1, -1)
    sc = sc / math.sqrt(s.head_dim)
    causal = pos[None, :] <= pos[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = _dot("bkgqs,bske->bqkge", pr, v, quant, -1, 1)
    o = o.reshape(b, n, s.heads * s.head_dim)
    wo = p["mixer"]["wo"].reshape(s.heads * s.head_dim, s.d_model)
    x = x + _dot("bsf,fd->bsd", o, wo, quant, -1, 0)
    h2 = _rms(x, p["norm2"]["scale"], s.norm_eps)
    gate = _dot("bsd,df->bsf", h2, p["ffn"]["gate"], quant, -1, 0)
    up = _dot("bsd,df->bsf", h2, p["ffn"]["up"], quant, -1, 0)
    return x + _dot("bsf,fd->bsd", jax.nn.silu(gate) * up,
                    p["ffn"]["down"], quant, -1, 0)


def _head(s: Shape, quant: bool, x, final_gain, embed, idx):
    """Logits at positions ``idx`` (B, P) of x (B, S, D)."""
    h = jnp.take_along_axis(x, idx[..., None], axis=1)
    h = _rms(h, final_gain, s.norm_eps)
    return _dot("bpd,vd->bpv", h, embed, quant, -1, -1)


_JIT: Dict = {}


def _jitted(s: Shape, quant: bool):
    key = (s, quant)
    if key not in _JIT:
        _JIT[key] = (
            jax.jit(lambda emb, t: emb[t].astype(jnp.float32)),
            jax.jit(lambda x, blk, l: _layer(s, quant, x, blk, l)),
            jax.jit(lambda x, g, emb, idx: _head(s, quant, x, g, emb, idx)),
        )
    return _JIT[key]


def reference_logits(
    s: Shape, weights: Dict, tokens: np.ndarray, positions: np.ndarray,
    quant: bool = False,
) -> np.ndarray:
    """Logits (B, P, V) float32 at ``positions`` (B, P) of each row of
    ``tokens`` (B, S), layer by layer so that one block's float32
    weights are the most that is held at a time."""
    embed_fn, layer_fn, head_fn = _jitted(s, quant)
    blk = weights["super"][0]
    x = embed_fn(weights["embed"], jnp.asarray(tokens, jnp.int32))
    for l in range(s.layers):
        x = layer_fn(x, blk, jnp.int32(l))
    out = head_fn(x, weights["final_norm"]["scale"], weights["embed"],
                  jnp.asarray(positions, jnp.int32))
    return np.asarray(out)


def gap(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at that position: 0 where the token is the reference's argmax."""
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, tokens[..., None].astype(np.int64), -1)[..., 0]
    return best - got


# ---------------------------------------------------------------------------
# Costs from shapes (FLOPs count a multiply-add as 2)
# ---------------------------------------------------------------------------


def block_matmul_params(s: Shape) -> int:
    attn = s.d_model * (s.heads + 2 * s.kv_heads) * s.head_dim
    attn += s.heads * s.head_dim * s.d_model
    return attn + 3 * s.d_model * s.d_ff


def param_bytes(s: Shape) -> int:
    """Every weight the step reads once: blocks, norms, tied table."""
    per_block = block_matmul_params(s) + 2 * s.d_model
    n = s.layers * per_block + s.vocab * s.d_model + s.d_model
    return n * jnp.dtype(s.dtype).itemsize


def kv_bytes(s: Shape, positions: int) -> int:
    """K and V of ``positions`` cached positions, over every layer."""
    return (2 * s.layers * s.kv_heads * s.head_dim * positions
            * jnp.dtype(s.dtype).itemsize)


def token_flops(s: Shape, context: int) -> int:
    """One token that attends to ``context`` positions (itself included),
    with its logits over the whole vocabulary."""
    mm = s.layers * block_matmul_params(s) + s.vocab * s.d_model
    attn = s.layers * 4 * s.heads * s.head_dim * context
    return 2 * mm + attn


def prefill_flops(s: Shape, rows: int, tokens: int) -> int:
    """A causal prompt of ``tokens`` per row, logits at the last only."""
    mm = 2 * s.layers * block_matmul_params(s) * tokens
    attn = s.layers * 4 * s.heads * s.head_dim * tokens * (tokens + 1) // 2
    head = 2 * s.vocab * s.d_model
    return rows * (mm + attn + head)


def prefill_bytes(s: Shape, rows: int, tokens: int) -> int:
    """Weights once, the prompt's tokens in, one token out per row."""
    return param_bytes(s) + rows * (tokens + 1) * 4


def decode_step_bytes(s: Shape, contexts: Sequence[int]) -> int:
    """Weights once, the K/V of each active row's live positions read,
    and one position per active row written."""
    return (param_bytes(s) + sum(kv_bytes(s, c) for c in contexts)
            + kv_bytes(s, 1) * len(contexts))


def decode_step_flops(s: Shape, contexts: Sequence[int]) -> int:
    return sum(token_flops(s, c) for c in contexts)


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline's least time: the larger of the compute bound and
    the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def pad_len(n: int, floor: int = 128) -> int:
    """Power-of-two length, so that reference shapes repeat across runs."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())

