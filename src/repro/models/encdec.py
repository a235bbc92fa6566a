"""Whisper-style encoder-decoder backbone (conv frontend STUBBED).

Per the assignment, the modality frontend is a stub: ``input_specs()``
provides precomputed frame embeddings (B, T_enc, d_model) — the two
strided convolutions of real Whisper are out of scope. Everything after
that is the real architecture: sinusoidal positions + bidirectional
encoder; learned positions + causal self-attention + cross-attention
decoder; LayerNorm / GELU / attention biases per Whisper.

Decode caches: per decoder layer a full self-attention KV cache plus the
cross-attention K/V, which are computed ONCE from the encoder output at
prefill (``encode_for_decode``) and read-only afterwards.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import kvcache
from repro.models.attention import (
    attention_spec,
    mha,
    mha_decode,
    project_kv,
)
from repro.models.layers import (
    Param,
    abstract_params,
    apply_mlp,
    apply_norm,
    build_axes,
    build_params,
    embed_lookup,
    embed_spec,
    mlp_spec,
    norm_spec,
    sinusoidal_positions,
    unembed,
)
from repro.models.sharding_hooks import constrain


def _enc_block_spec(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    return {
        "norm1": norm_spec(d, cfg.norm),
        "attn": attention_spec(
            d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, bias=True
        ),
        "norm2": norm_spec(d, cfg.norm),
        "ffn": mlp_spec(d, cfg.d_ff, cfg.activation),
    }


def _dec_block_spec(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    return {
        "norm1": norm_spec(d, cfg.norm),
        "self_attn": attention_spec(
            d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, bias=True
        ),
        "norm_cross": norm_spec(d, cfg.norm),
        "cross_attn": attention_spec(
            d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, bias=True
        ),
        "norm2": norm_spec(d, cfg.norm),
        "ffn": mlp_spec(d, cfg.d_ff, cfg.activation),
    }


def _stack(spec: Any, n: int) -> Any:
    return jax.tree.map(
        lambda p: Param((n,) + p.shape, ("layer",) + p.axes, p.init, p.scale),
        spec,
        is_leaf=lambda x: isinstance(x, Param),
    )


class EncDecTransformer:
    """Whisper-family model. cfg.n_layers = decoder layers,
    cfg.n_encoder_layers = encoder layers."""

    # ``next_token_logits`` computes every decoder row (no cut).
    prefill_cut = None

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.MAX_DEC_POSITIONS = cfg.max_dec_positions
        self._spec = self._model_spec()

    def _model_spec(self) -> Dict:
        cfg = self.cfg
        return {
            "embed": embed_spec(cfg.vocab_size, cfg.d_model),
            "dec_pos": Param(
                (self.MAX_DEC_POSITIONS, cfg.d_model), (None, "embed"), scale=0.02
            ),
            "encoder": _stack(_enc_block_spec(cfg), cfg.n_encoder_layers),
            "enc_final_norm": norm_spec(cfg.d_model, cfg.norm),
            "decoder": _stack(_dec_block_spec(cfg), cfg.n_layers),
            "dec_final_norm": norm_spec(cfg.d_model, cfg.norm),
        }

    # ----- params -------------------------------------------------------
    def spec(self):
        return self._spec

    def init(self, key, dtype=None):
        return build_params(self._spec, key, dtype or self.cfg.dtype)

    def abstract_params(self, dtype=None):
        return abstract_params(self._spec, dtype or self.cfg.dtype)

    def axes(self):
        return build_axes(self._spec)

    # ----- encoder --------------------------------------------------------
    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: (B, T, d_model) precomputed embeddings (frontend stub)."""
        cfg = self.cfg
        b, t, d = frames.shape
        x = frames + sinusoidal_positions(t, d).astype(frames.dtype)[None]
        x = constrain(x, ("batch", "seq", "embed"))
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

        def block(x, p):
            h = apply_norm(x, p["norm1"], cfg.norm)
            y = mha(
                p["attn"], h, positions, causal=False, rope_theta=None,
                rope_kind="none", impl=cfg.impl,
            )
            x = x + y
            h2 = apply_norm(x, p["norm2"], cfg.norm)
            x = x + apply_mlp(h2, p["ffn"], cfg.activation)
            return constrain(x, ("batch", "seq", "embed")), None

        body = block
        if cfg.remat:
            body = jax.checkpoint(block, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["encoder"])
        return apply_norm(x, params["enc_final_norm"], cfg.norm)

    # ----- decoder, full sequence (training) ------------------------------
    def _dec_block_full(self, p, x, positions, enc_out, enc_positions):
        cfg = self.cfg
        h = apply_norm(x, p["norm1"], cfg.norm)
        y = mha(
            p["self_attn"], h, positions, causal=True, rope_theta=None,
            rope_kind="none", impl=cfg.impl,
        )
        x = x + y
        hc = apply_norm(x, p["norm_cross"], cfg.norm)
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wv"])
        if "bv" in p["cross_attn"]:
            v = v + p["cross_attn"]["bv"]
        y = mha(
            p["cross_attn"], hc, positions, causal=False, rope_theta=None,
            rope_kind="none", impl=cfg.impl, kv_override=(k, v),
        )
        x = x + y
        h2 = apply_norm(x, p["norm2"], cfg.norm)
        x = x + apply_mlp(h2, p["ffn"], cfg.activation)
        return constrain(x, ("batch", "seq", "embed"))

    def forward(
        self, params, frames: jax.Array, dec_tokens: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Training forward: returns (decoder logits f32, aux=0)."""
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        b, s = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        enc_positions = jnp.broadcast_to(
            jnp.arange(enc_out.shape[1])[None, :], (b, enc_out.shape[1])
        )
        x = embed_lookup(params["embed"], dec_tokens)
        x = x + params["dec_pos"][:s][None].astype(x.dtype)

        def block(x, p):
            return (
                self._dec_block_full(p, x, positions, enc_out, enc_positions),
                None,
            )

        body = block
        if cfg.remat:
            body = jax.checkpoint(block, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["decoder"])
        x = apply_norm(x, params["dec_final_norm"], cfg.norm)
        return unembed(x, params["embed"]), jnp.zeros((), jnp.float32)

    def next_token_logits(self, params, frames, dec_tokens) -> jax.Array:
        """(B, V) f32 logits of the token after ``dec_tokens``."""
        return self.forward(params, frames, dec_tokens)[0][:, -1]

    def loss(self, params, frames, dec_tokens, aux_weight: float = 0.0):
        logits, _ = self.forward(params, frames, dec_tokens)
        targets = dec_tokens[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    # ----- decode ----------------------------------------------------------
    def init_cache(
        self, batch: int, max_len: int, enc_len: int, abstract: bool = False
    ):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        n = cfg.n_layers

        def stacked(shape, dtype):
            if abstract:
                return jax.ShapeDtypeStruct((n,) + shape, dtype)
            return jnp.zeros((n,) + shape, dtype)

        return {
            "self_k": stacked((batch, max_len, cfg.n_kv_heads, hd), cfg.dtype),
            "self_v": stacked((batch, max_len, cfg.n_kv_heads, hd), cfg.dtype),
            "cross_k": stacked((batch, enc_len, cfg.n_kv_heads, hd), cfg.dtype),
            "cross_v": stacked((batch, enc_len, cfg.n_kv_heads, hd), cfg.dtype),
        }

    def encode_for_decode(self, params, frames, cache):
        """Run the encoder and populate the cross K/V cache."""
        enc_out = self.encode(params, frames)

        def per_layer(p):
            k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross_attn"]["wv"])
            if "bv" in p["cross_attn"]:
                v = v + p["cross_attn"]["bv"]
            return k.astype(self.cfg.dtype), v.astype(self.cfg.dtype)

        ks, vs = jax.vmap(per_layer)(params["decoder"])
        return dict(cache, cross_k=ks, cross_v=vs)

    def decode_step(
        self, params, cache, token: jax.Array, cursor: jax.Array,
        active: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Any]:
        """One decoder token against self+cross caches.
        token: (B,), cursor: (B,); ``active``: (B,) live-slot bitmap
        (slot arena) — dead rows are masked out of both attentions."""
        cfg = self.cfg
        b = token.shape[0]
        x = embed_lookup(params["embed"], token[:, None])
        x = x + jnp.take(params["dec_pos"], cursor, axis=0)[:, None].astype(x.dtype)
        enc_len = cache["cross_k"].shape[2]
        enc_pos = jnp.broadcast_to(jnp.arange(enc_len)[None, :], (b, enc_len))
        enc_valid = jnp.ones((b, enc_len), bool)
        if active is not None:
            enc_valid = enc_valid & active[:, None]

        def block(x, scanned):
            p, sk, sv, ck, cv = scanned
            h = apply_norm(x, p["norm1"], cfg.norm)
            k, v = project_kv(p["self_attn"], h, cursor[:, None], None, "none")
            updated = kvcache.attn_cache_write({"k": sk, "v": sv}, k, v, cursor)
            cache_k, cache_v, kv_pos, valid = kvcache.attn_cache_views(
                updated, cursor
            )
            y = mha_decode(
                p["self_attn"], h, cursor, cache_k, cache_v, kv_pos, valid,
                rope_theta=None, rope_kind="none", impl=cfg.impl,
                active=active,
            )
            x = x + y
            hc = apply_norm(x, p["norm_cross"], cfg.norm)
            y = mha_decode(
                p["cross_attn"], hc, cursor, ck, cv, enc_pos, enc_valid,
                causal=False, rope_theta=None, rope_kind="none", impl=cfg.impl,
            )
            x = x + y
            h2 = apply_norm(x, p["norm2"], cfg.norm)
            x = x + apply_mlp(h2, p["ffn"], cfg.activation)
            return x, (updated["k"], updated["v"])

        x, (new_k, new_v) = jax.lax.scan(
            block,
            x,
            (
                params["decoder"],
                cache["self_k"],
                cache["self_v"],
                cache["cross_k"],
                cache["cross_v"],
            ),
        )
        x = apply_norm(x, params["dec_final_norm"], cfg.norm)
        logits = unembed(x, params["embed"])
        new_cache = dict(cache, self_k=new_k, self_v=new_v)
        return logits[:, 0], new_cache
