"""Shared building blocks for the LM-family model zoo.

Parameters are declared as ``Param`` specs (shape + logical sharding axes
+ initializer); a single spec tree is the source of truth for

  * materialized parameters   (``build_params`` — real arrays),
  * abstract parameters       (``abstract_params`` — ShapeDtypeStructs for
                               the dry-run; 405B is never allocated),
  * logical sharding axes     (``build_axes`` — consumed by
                               repro.distributed.sharding).

All model code is purely functional: ``f(params, inputs) -> outputs``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | embed
    scale: Optional[float] = None  # override stddev

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _scaled_normal(key, shape, dtype, std) -> jax.Array:
    # One fused program per leaf: only the ``dtype`` result is ever
    # materialized (eagerly, a (40, 2048, 8192) leaf would hold two f32
    # copies on the device before the cast).
    return (std * jax.random.normal(key, shape)).astype(dtype)


def _init_leaf(key, p: Param, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "embed":
        std = p.scale if p.scale is not None else 1.0
        return _scaled_normal(key, p.shape, dtype, std)
    # fan-in scaled normal
    fan_in = p.shape[0] if len(p.shape) > 1 else max(p.shape[0], 1)
    if len(p.shape) == 3:  # stacked experts / stacked layers: fan-in is dim 1
        fan_in = p.shape[1]
    std = p.scale if p.scale is not None else 1.0 / math.sqrt(fan_in)
    return _scaled_normal(key, p.shape, dtype, std)


def build_params(spec: Any, key: jax.Array, dtype=jnp.float32) -> Any:
    """Materialize a spec tree into real parameter arrays."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Param)
    )
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(k, p, dtype) for k, p in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def abstract_params(spec: Any, dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct tree (dry-run stand-ins; no allocation)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
        spec,
        is_leaf=lambda x: isinstance(x, Param),
    )


def build_axes(spec: Any) -> Any:
    """Tree of logical-axis tuples matching the param tree structure."""
    return jax.tree.map(
        lambda p: p.axes, spec, is_leaf=lambda x: isinstance(x, Param)
    )


def param_count(spec: Any) -> int:
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, Param))
    return sum(int(math.prod(p.shape)) for p in leaves)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layernorm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def norm_spec(d: int, kind: str) -> Dict[str, Param]:
    if kind == "rmsnorm":
        return {"scale": Param((d,), ("embed",), init="zeros")}
    return {
        "scale": Param((d,), ("embed",), init="ones"),
        "bias": Param((d,), ("embed",), init="zeros"),
    }


def apply_norm(x: jax.Array, p: Dict[str, jax.Array], kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and multimodal M-RoPE)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for half the head dim."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim//2,)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float
) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    sin = jnp.sin(angles)[..., None, :]  # (B, S, 1, D/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float,
    sections: Tuple[int, int, int] = (2, 1, 1),
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): the head dim's frequency bands are
    partitioned into temporal/height/width sections, each rotated by its
    own position stream. positions: (3, B, S). ``sections`` are relative
    weights over the head_dim//2 frequency bands (t:h:w = 2:1:1 here)."""
    half = x.shape[-1] // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections[:-1]:
        acc += (half * s) // total
        bounds.append(acc)
    freqs = rope_frequencies(x.shape[-1], theta)  # (half,)
    # Select which position stream drives each frequency band.
    band = jnp.zeros((half,), jnp.int32)
    band = band.at[bounds[0]:].set(1)
    band = band.at[bounds[1]:].set(2)
    # positions: (3, B, S) -> per-band positions (B, S, half)
    pos = jnp.take_along_axis(
        positions.transpose(1, 2, 0).astype(jnp.float32),  # (B, S, 3)
        jnp.broadcast_to(band, positions.shape[1:3] + (half,)).astype(jnp.int32),
        axis=-1,
    )  # (B, S, half)
    angles = pos * freqs  # (B, S, half)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal positional embedding (T, D)."""
    log_timescale = math.log(10000.0) / max(dim // 2 - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_spec(d_model: int, d_ff: int, activation: str) -> Dict[str, Param]:
    if activation in ("swiglu", "geglu"):
        return {
            "gate": Param((d_model, d_ff), ("embed", "mlp")),
            "up": Param((d_model, d_ff), ("embed", "mlp")),
            "down": Param((d_ff, d_model), ("mlp", "embed")),
        }
    return {
        "up": Param((d_model, d_ff), ("embed", "mlp")),
        "up_bias": Param((d_ff,), ("mlp",), init="zeros"),
        "down": Param((d_ff, d_model), ("mlp", "embed")),
        "down_bias": Param((d_model,), ("embed",), init="zeros"),
    }


def apply_mlp(x: jax.Array, p: Dict[str, jax.Array], activation: str) -> jax.Array:
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
        return h @ p["down"]
    if activation == "geglu":
        h = jax.nn.gelu(x @ p["gate"], approximate=True) * (x @ p["up"])
        return h @ p["down"]
    h = jax.nn.gelu(x @ p["up"] + p["up_bias"], approximate=True)
    return h @ p["down"] + p["down_bias"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(vocab: int, d_model: int) -> Param:
    return Param((vocab, d_model), ("vocab", "embed"), init="embed", scale=0.02)


def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """Tied unembedding: bf16 operands, f32 accumulation (MXU-native) —
    avoids materializing an f32 copy of the (sharded) vocab table."""
    return jnp.einsum(
        "bsd,vd->bsv", x, table, preferred_element_type=jnp.float32
    )
