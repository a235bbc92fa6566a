"""Decoder-only transformer assembly for the architecture pool.

Layers are grouped into *superblocks* — one repetition of
``cfg.block_pattern`` — and scanned with stacked parameters, so a
126-layer model lowers as one scan over 126 bodies (compile time and HLO
size stay flat in depth). Non-divisible tail layers run unscanned.

Modes:
- ``forward``      — full-sequence logits (training / prefill shapes)
- ``next_token_logits`` — the last position's logits only (the serving
                     engine's prefill program)
- ``loss``         — next-token cross entropy (+ MoE aux)
- ``prefill``      — forward + populate decode caches
- ``decode_step``  — one token against caches (serve_step for the
                     decode_32k / long_500k dry-run shapes)

Encoder-decoder (whisper) lives in encdec.py; ``repro.models.model_for``
dispatches.
"""
from __future__ import annotations

import math
from functools import partial
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
    project_qkv,
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
    unembed,
)
from repro.models.moe import apply_moe, moe_spec
from repro.models.recurrent import (
    CONV_WIDTH,
    griffin_block,
    griffin_block_spec,
    rwkv6_channelmix,
    rwkv6_channelmix_spec,
    rwkv6_timemix,
    rwkv6_timemix_spec,
)
from repro.models.sharding_hooks import constrain


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def block_spec(cfg: ModelConfig, kind: str) -> Dict:
    d = cfg.d_model
    spec: Dict[str, Any] = {"norm1": norm_spec(d, cfg.norm)}
    if kind in ("attn", "swa"):
        spec["mixer"] = attention_spec(
            d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.attn_bias
        )
    elif kind == "rglru":
        spec["mixer"] = griffin_block_spec(d, cfg.d_rnn or d)
    elif kind == "rwkv":
        spec["mixer"] = rwkv6_timemix_spec(d, cfg.n_heads)
    else:
        raise ValueError(f"unknown block kind {kind}")
    spec["norm2"] = norm_spec(d, cfg.norm)
    if kind == "rwkv":
        spec["ffn"] = rwkv6_channelmix_spec(d, cfg.d_ff)
    elif cfg.is_moe and kind in ("attn", "swa"):
        spec["ffn"] = moe_spec(
            d, cfg.d_ff, cfg.n_experts, cfg.activation, cfg.shared_expert
        )
    else:
        spec["ffn"] = mlp_spec(d, cfg.d_ff, cfg.activation)
    return spec


def _stack_spec(spec: Any, n: int) -> Any:
    return jax.tree.map(
        lambda p: Param((n,) + p.shape, ("layer",) + p.axes, p.init, p.scale),
        spec,
        is_leaf=lambda x: isinstance(x, Param),
    )


def model_spec(cfg: ModelConfig) -> Dict:
    spec: Dict[str, Any] = {"embed": embed_spec(cfg.vocab_size, cfg.d_model)}
    if cfg.n_super > 0:
        spec["super"] = [
            _stack_spec(block_spec(cfg, kind), cfg.n_super)
            for kind in cfg.block_pattern
        ]
    spec["tail"] = [block_spec(cfg, kind) for kind in cfg.tail_kinds]
    spec["final_norm"] = norm_spec(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        spec["lm_head"] = Param(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), scale=0.02
        )
    return spec


# ---------------------------------------------------------------------------
# Full-sequence block application (train / prefill)
# ---------------------------------------------------------------------------


def _apply_block_full(
    cfg: ModelConfig,
    kind: str,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    collect: bool,
) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Returns (x_out, moe_aux, cache_contrib or None)."""
    h = apply_norm(x, p["norm1"], cfg.norm)
    contrib = None
    if kind in ("attn", "swa"):
        window = cfg.sliding_window if kind == "swa" else None
        y = mha(
            p["mixer"],
            h,
            positions,
            causal=True,
            window=window,
            rope_theta=cfg.rope_theta,
            rope_kind=cfg.rope_kind,
            impl=cfg.impl,
        )
        if collect:
            pos1d = positions if positions.ndim == 2 else positions[0]
            k, v = project_kv(
                p["mixer"], h, pos1d if cfg.rope_kind != "mrope" else positions,
                cfg.rope_theta, cfg.rope_kind,
            )
            contrib = {"k": k, "v": v}
    elif kind == "rglru":
        y, state = griffin_block(p["mixer"], h, impl=cfg.impl)
        contrib = state if collect else None
    else:  # rwkv
        y, state = rwkv6_timemix(p["mixer"], h, cfg.n_heads, impl=cfg.impl)
        contrib = state if collect else None
    x = x + y
    h2 = apply_norm(x, p["norm2"], cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if kind == "rwkv":
        f, chan_state = rwkv6_channelmix(p["ffn"], h2)
        if collect:
            contrib = dict(contrib or {}, channel=chan_state)
    elif cfg.is_moe and kind in ("attn", "swa"):
        if cfg.moe_dense:
            from repro.models.moe import apply_moe_dense_reference

            f = apply_moe_dense_reference(
                p["ffn"], h2, top_k=cfg.top_k, activation=cfg.activation
            )
        else:
            f, aux = apply_moe(
                p["ffn"],
                h2,
                top_k=cfg.top_k,
                activation=cfg.activation,
                capacity_factor=cfg.moe_capacity_factor,
            )
    else:
        f = apply_mlp(h2, p["ffn"], cfg.activation)
    x = x + f
    x = constrain(x, ("batch", "seq", "embed"))
    return x, aux, contrib


def _apply_block_last_row(
    cfg: ModelConfig,
    kind: str,
    p: Dict,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,
) -> jax.Array:
    """A dense attention block read at the last position only: ``norm1``
    and the rotated K/V run over all S positions, the query, attention,
    output projection and MLP on row S-1. Returns (B, 1, D), equal to
    ``_apply_block_full(...)[0][:, -1:]``."""
    window = cfg.sliding_window if kind == "swa" else None
    pos1d = positions if positions.ndim == 2 else positions[0]
    mrope = cfg.rope_kind == "mrope"
    b, s, d = x.shape

    def last(a):  # (B, S, D) -> (B, 1, D)
        # A strided slice of the flattened rows: ``a[:, -1:]`` makes XLA
        # on the TPU relayout the whole hidden state (and V) at B >= 2.
        return a.reshape(b * s, d)[s - 1 :: s][:, None]

    h = apply_norm(x, p["norm1"], cfg.norm)
    k, v = project_kv(
        p["mixer"], h, positions if mrope else pos1d, cfg.rope_theta,
        cfg.rope_kind,
    )
    # One query row against S keys: the decode path's dense attention.
    y = mha_decode(
        p["mixer"],
        last(h),
        pos1d[:, -1],
        k,
        v,
        pos1d,
        None,
        window=window,
        rope_theta=cfg.rope_theta,
        rope_kind=cfg.rope_kind,
        mrope_position=positions[..., -1:] if mrope else None,
        impl="xla",
    )
    x = last(x) + y
    h2 = apply_norm(x, p["norm2"], cfg.norm)
    return x + apply_mlp(h2, p["ffn"], cfg.activation)


# ---------------------------------------------------------------------------
# Decode-step block application
# ---------------------------------------------------------------------------


def _apply_block_decode(
    cfg: ModelConfig,
    kind: str,
    p: Dict,
    x: jax.Array,  # (B, 1, D)
    cursor: jax.Array,  # (B,) absolute position of this token
    cache: Dict,
    mrope_position: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,  # (B,) live-slot bitmap (arena)
) -> Tuple[jax.Array, Dict]:
    h = apply_norm(x, p["norm1"], cfg.norm)
    if kind in ("attn", "swa"):
        window = cfg.sliding_window if kind == "swa" else None
        pos_for_kv = (
            cursor[:, None] if cfg.rope_kind != "mrope" else mrope_position
        )
        k, v = project_kv(p["mixer"], h, pos_for_kv, cfg.rope_theta, cfg.rope_kind)
        if kind == "attn":
            cache = kvcache.attn_cache_write(cache, k, v, cursor)
            ck, cv, kv_pos, valid = kvcache.attn_cache_views(cache, cursor)
        else:
            cache = kvcache.ring_cache_write(cache, k, v, cursor)
            ck, cv, kv_pos, valid = kvcache.ring_cache_views(cache, cursor)
        y = mha_decode(
            p["mixer"],
            h,
            cursor,
            ck,
            cv,
            kv_pos,
            valid,
            window=window,
            rope_theta=cfg.rope_theta,
            rope_kind=cfg.rope_kind,
            mrope_position=mrope_position,
            impl=cfg.impl,
            active=active,
        )
    elif kind == "rglru":
        y2d, state = griffin_block(
            p["mixer"], h, state={"h": cache["h"], "conv": cache["conv"]},
            impl=cfg.impl,
        )
        y = y2d
        cache = dict(cache, h=state["h"], conv=state["conv"])
    else:  # rwkv
        y, tstate = rwkv6_timemix(
            p["mixer"],
            h,
            cfg.n_heads,
            state={"shift": cache["shift"], "wkv": cache["wkv"]},
            impl=cfg.impl,
        )
        cache = dict(cache, shift=tstate["shift"], wkv=tstate["wkv"])
    x = x + y
    h2 = apply_norm(x, p["norm2"], cfg.norm)
    if kind == "rwkv":
        f, chan = rwkv6_channelmix(p["ffn"], h2, state=cache["channel"])
        cache = dict(cache, channel=chan)
    elif cfg.is_moe and kind in ("attn", "swa"):
        f, _ = apply_moe(
            p["ffn"],
            h2,
            top_k=cfg.top_k,
            activation=cfg.activation,
            capacity_factor=2.0,  # decode: tiny token count, avoid drops
        )
    else:
        f = apply_mlp(h2, p["ffn"], cfg.activation)
    x = x + f
    return x, cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, abstract: bool):
    dtype = cfg.dtype
    hd = cfg.resolved_head_dim
    if kind == "attn":
        fn = kvcache.attn_cache_abstract if abstract else kvcache.attn_cache_init
        return fn(batch, max_len, cfg.n_kv_heads, hd, dtype)
    if kind == "swa":
        window = min(cfg.sliding_window, max_len)
        fn = kvcache.ring_cache_abstract if abstract else kvcache.ring_cache_init
        return fn(batch, window, cfg.n_kv_heads, hd, dtype)
    if kind == "rglru":
        dr = cfg.d_rnn or cfg.d_model
        shapes = {
            "h": (batch, dr),
            "conv": (batch, CONV_WIDTH - 1, dr),
        }
    else:  # rwkv
        hd6 = cfg.d_model // cfg.n_heads
        shapes = {
            "shift": (batch, cfg.d_model),
            "wkv": (batch, cfg.n_heads, hd6, hd6),
            "channel": (batch, cfg.d_model),
        }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    return {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False):
    def stack(tree, n):
        return jax.tree.map(
            lambda l: (
                jax.ShapeDtypeStruct((n,) + l.shape, l.dtype)
                if abstract
                else jnp.broadcast_to(l, (n,) + l.shape)
            ),
            tree,
        )

    cache = {}
    if cfg.n_super > 0:
        cache["super"] = [
            stack(_layer_cache(cfg, kind, batch, max_len, abstract), cfg.n_super)
            for kind in cfg.block_pattern
        ]
    cache["tail"] = [
        _layer_cache(cfg, kind, batch, max_len, abstract) for kind in cfg.tail_kinds
    ]
    return cache


# ---------------------------------------------------------------------------
# Model API
# ---------------------------------------------------------------------------


class Transformer:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._spec = model_spec(cfg)

    # ----- params -----------------------------------------------------
    def spec(self):
        return self._spec

    def init(self, key, dtype=None):
        return build_params(self._spec, key, dtype or self.cfg.dtype)

    def abstract_params(self, dtype=None):
        return abstract_params(self._spec, dtype or self.cfg.dtype)

    def axes(self):
        return build_axes(self._spec)

    # ----- forward ------------------------------------------------------
    def _embed(self, params, tokens):
        x = embed_lookup(params["embed"], tokens)
        if self.cfg.embed_scale:
            x = x * math.sqrt(self.cfg.d_model)
        return constrain(x, ("batch", "seq", "embed"))

    def _unembed(self, params, x):
        """(B, S, D) final-normed hidden states -> (B, S, V) f32 logits."""
        if self.cfg.tie_embeddings:
            return unembed(x, params["embed"])
        return jnp.einsum(
            "bsd,dv->bsv", x.astype(jnp.float32),
            params["lm_head"].astype(jnp.float32),
        )

    def forward(
        self, params, tokens: jax.Array, positions: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, jax.Array]:
        """tokens: (B, S) int32; positions: (B, S) or (3, B, S) for mrope.
        Returns (logits f32, moe_aux)."""
        cfg = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed(params, tokens)
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.n_super > 0:

            def superblock(carry, layer_params):
                x, aux = carry
                for j, kind in enumerate(cfg.block_pattern):
                    x, a, _ = _apply_block_full(
                        cfg, kind, layer_params[j], x, positions, collect=False
                    )
                    aux = aux + a
                return (x, aux), None

            body = superblock
            if cfg.remat:
                body = jax.checkpoint(superblock, prevent_cse=False)
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), params["super"]
            )
        for p_layer, kind in zip(params["tail"], cfg.tail_kinds):
            x, a, _ = _apply_block_full(cfg, kind, p_layer, x, positions, False)
            aux_total = aux_total + a
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = self._unembed(params, x)
        return logits, aux_total

    @property
    def prefill_cut(self) -> str:
        """What ``next_token_logits`` skips: ``"tail_one_row"`` (the last
        block after its K/V, the final norm and the unembedding run on
        one row) where the last layer is dense attention;
        ``"unembed_only"`` where it is MoE (capacity depends on the token
        count) or recurrent (state and token shift run over the
        sequence), so only the final norm and the unembedding are cut."""
        dense_attn = self.cfg.pattern_layers[-1] in ("attn", "swa")
        if dense_attn and not self.cfg.is_moe:
            return "tail_one_row"
        return "unembed_only"

    def next_token_logits(
        self, params, tokens: jax.Array, positions: Optional[jax.Array] = None
    ) -> jax.Array:
        """(B, V) f32 logits of the token after ``tokens``: equal to
        ``forward(...)[0][:, -1]``, computing only the rows it reads
        (``prefill_cut``). The layer stack is indexed inside the scan and
        the last layer taken by a static index, so no slice of the
        stacked weights is copied."""
        cfg = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        x = self._embed(params, tokens)

        def block(kind, p, x):
            return _apply_block_full(cfg, kind, p, x, positions, False)[0]

        # Whole superblocks, up to the one holding the last layer when
        # there is no tail.
        n_scan = cfg.n_super - (0 if cfg.tail_kinds else 1)
        if n_scan > 0:

            def superblock(x, i):
                layer_params = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                    params["super"],
                )
                for j, kind in enumerate(cfg.block_pattern):
                    x = block(kind, layer_params[j], x)
                return x, None

            x, _ = jax.lax.scan(superblock, x, jnp.arange(n_scan))
        if cfg.tail_kinds:
            rest = list(zip(cfg.tail_kinds, params["tail"]))
        else:
            last = jax.tree.map(lambda a: a[cfg.n_super - 1], params["super"])
            rest = list(zip(cfg.block_pattern, last))
        for kind, p in rest[:-1]:
            x = block(kind, p, x)
        kind, p = rest[-1]
        if self.prefill_cut == "tail_one_row":
            x = _apply_block_last_row(cfg, kind, p, x, positions)
        else:
            x = block(kind, p, x)[:, -1:]
        x = apply_norm(x, params["final_norm"], cfg.norm)
        return self._unembed(params, x)[:, 0]

    # ----- loss -----------------------------------------------------------
    def loss(self, params, tokens, positions=None, aux_weight: float = 0.01):
        logits, aux = self.forward(params, tokens, positions)
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll) + aux_weight * aux

    # ----- decode ----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, abstract: bool = False):
        return init_cache(self.cfg, batch, max_len, abstract)

    def decode_step(
        self,
        params,
        cache,
        token: jax.Array,  # (B,) int32
        cursor: jax.Array,  # (B,) absolute position of this token
        mrope_position: Optional[jax.Array] = None,  # (3, B, 1)
        active: Optional[jax.Array] = None,  # (B,) bool live-slot bitmap
    ) -> Tuple[jax.Array, Any]:
        """One-token decode: returns (logits (B, V) f32, new cache).

        ``active`` marks live slot-arena rows (serving/engine.py): dead
        rows are fully masked out of attention (the Pallas kernel skips
        all their KV blocks) and their logits are unspecified — the
        engine never reads them. ``None`` means every row is live.
        """
        cfg = self.cfg
        x = self._embed(params, token[:, None])
        if cfg.rope_kind == "mrope" and mrope_position is None:
            mrope_position = jnp.broadcast_to(
                cursor[None, :, None], (3,) + cursor.shape + (1,)
            )
        new_cache = dict(cache)
        if cfg.n_super > 0:

            def superblock(x, scanned):
                layer_params, layer_cache = scanned
                new_layer_cache = []
                for j, kind in enumerate(cfg.block_pattern):
                    x, c = _apply_block_decode(
                        cfg, kind, layer_params[j], x, cursor,
                        layer_cache[j], mrope_position, active,
                    )
                    new_layer_cache.append(c)
                return x, new_layer_cache

            x, new_super = jax.lax.scan(
                superblock, x, (params["super"], cache["super"])
            )
            new_cache["super"] = new_super
        new_tail = []
        for p_layer, kind, c in zip(params["tail"], cfg.tail_kinds, cache["tail"]):
            x, c2 = _apply_block_decode(
                cfg, kind, p_layer, x, cursor, c, mrope_position, active
            )
            new_tail.append(c2)
        new_cache["tail"] = new_tail
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = self._unembed(params, x)
        return logits[:, 0], new_cache

    # ----- prefill (forward + cache population) ----------------------------
    def prefill(
        self, params, cache, tokens: jax.Array, positions=None
    ) -> Tuple[jax.Array, Any]:
        """Left-aligned prefill: fills caches for positions [0, S) and
        returns (last-token logits (B, V), cache). Used by the serving
        engine; tail/super handled like forward but collecting KV."""
        cfg = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        pos1d = positions if positions.ndim == 2 else positions[0]
        x = self._embed(params, tokens)
        new_cache = dict(cache)

        def fill_attn(layer_cache, contrib, kind):
            if kind == "attn":
                k = jax.lax.dynamic_update_slice(
                    layer_cache["k"], contrib["k"].astype(layer_cache["k"].dtype),
                    (0, 0, 0, 0),
                )
                v = jax.lax.dynamic_update_slice(
                    layer_cache["v"], contrib["v"].astype(layer_cache["v"].dtype),
                    (0, 0, 0, 0),
                )
                return {"k": k, "v": v}
            return kvcache.ring_cache_fill_from_prefill(
                layer_cache, contrib["k"], contrib["v"], pos1d
            )

        def merge(kind, layer_cache, contrib):
            if kind in ("attn", "swa"):
                return fill_attn(layer_cache, contrib, kind)
            merged = dict(layer_cache)
            for key, val in contrib.items():
                merged[key] = val
            return merged

        if cfg.n_super > 0:

            def superblock(x, scanned):
                layer_params, layer_cache = scanned
                out_caches = []
                for j, kind in enumerate(cfg.block_pattern):
                    x, _, contrib = _apply_block_full(
                        cfg, kind, layer_params[j], x, positions, collect=True
                    )
                    out_caches.append(merge(kind, layer_cache[j], contrib))
                return x, out_caches

            x, new_super = jax.lax.scan(
                superblock, x, (params["super"], cache["super"])
            )
            new_cache["super"] = new_super
        new_tail = []
        for p_layer, kind, c in zip(params["tail"], cfg.tail_kinds, cache["tail"]):
            x, _, contrib = _apply_block_full(cfg, kind, p_layer, x, positions, True)
            new_tail.append(merge(kind, c, contrib))
        new_cache["tail"] = new_tail
        x = apply_norm(x[:, -1:], params["final_norm"], cfg.norm)
        logits = self._unembed(params, x)
        return logits[:, 0], new_cache
