"""Compile the serving programs for a described TPU v5e at granite-3-2b
widths — no chip attached, nothing runs.

The TPU compiler is installed here and accepts a topology description,
so what it would refuse on the chip (kernel block shapes off the
(8, 128) tiling, a program over the device's memory) fails here first.
The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports
this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import decode_attention as decode_kernel
from repro.kernels import flash_attention as flash_kernel
from repro.kernels import ops as kernel_ops
from repro.models import model_for
from repro.serving.engine import decode_program, prefill_program

ARCH = "granite-3-2b"
SLOTS, DECODE_SEQ, PREFILL_SEQ = 8, 2048, 512
V5E_HBM = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
    )
    assert total < V5E_HBM, m
    return total


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_engine_decode_program_compiles(one_chip, impl, monkeypatch):
    """The served decode program: 8 slots x 2048, arena donated. With
    ``impl="pallas"`` the step carries the decode_attention kernel (the
    wrapper picks interpret mode by the default backend, which is the
    CPU here — steer it to the compiled kernel)."""
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    model = model_for(dataclasses.replace(get_config(ARCH), impl=impl))
    params = _on(model.abstract_params(), one_chip)
    cache = _on(model.init_cache(SLOTS, DECODE_SEQ, abstract=True), one_chip)
    vec = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt, sharding=one_chip)  # noqa: E731
    compiled = decode_program(model, DECODE_SEQ, donate=True).lower(
        params, cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_)
    ).compile()
    m = compiled.memory_analysis()
    # Donation: the arena comes back in place, aliased to its input.
    assert m.alias_size_in_bytes > 0, m
    _fits(compiled)
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")


def _entry(text: str) -> str:
    """The ENTRY computation of a compiled module's text."""
    entry = text[text.index("\nENTRY"):]
    end = entry.find("\n}\n")
    return entry if end < 0 else entry[:end]


@pytest.mark.parametrize("batch", [1, SLOTS])
def test_engine_prefill_program_compiles(one_chip, batch):
    """The served prefill program computes the next token's logits only:
    no (b, 512, vocab) unembedding (XLA prunes it by itself at b >= 2
    but not at b = 1), and the last layer reads the stacked weights in
    place: no ENTRY instruction but a parameter, a loop output or a
    bitcast holds a whole (n_layers, ...) weight stack."""
    cfg = get_config(ARCH)
    model = model_for(cfg)
    params = _on(model.abstract_params(), one_chip)
    tokens = jax.ShapeDtypeStruct((batch, PREFILL_SEQ), jnp.int32, sharding=one_chip)
    compiled = prefill_program(model).lower(params, tokens).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert f"f32[{batch},{PREFILL_SEQ},{cfg.vocab_size}]" not in text
    stacked = re.compile(rf"= \w+\[{cfg.n_layers},[^\]]*\]\S* (\S+)\(")
    held = [
        line.strip() for line in _entry(text).splitlines()[1:]
        if (m := stacked.search(line))
        and m.group(1) not in ("parameter", "get-tuple-element", "bitcast")
    ]
    assert not held, held


def _kernel_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("active", [False, True])
def test_decode_attention_kernel_compiles(one_chip, active):
    cfg = get_config(ARCH)
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = [
        sds((SLOTS, 1, h, d), jnp.bfloat16),
        sds((SLOTS, DECODE_SEQ, kv, d), jnp.bfloat16),
        sds((SLOTS, DECODE_SEQ, kv, d), jnp.bfloat16),
        sds((SLOTS,), jnp.int32),
        sds((SLOTS, DECODE_SEQ), jnp.int32),
        sds((SLOTS, DECODE_SEQ), jnp.bool_),
    ]
    if active:
        args.append(sds((SLOTS,), jnp.bool_))
    text = _kernel_text(decode_kernel.decode_attention, *args)
    assert "tpu_custom_call" in text


def test_flash_attention_kernel_compiles(one_chip):
    cfg = get_config(ARCH)
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    text = _kernel_text(
        flash_kernel.flash_attention,
        sds((SLOTS, PREFILL_SEQ, h, d)),
        sds((SLOTS, PREFILL_SEQ, kv, d)),
        sds((SLOTS, PREFILL_SEQ, kv, d)),
    )
    assert "tpu_custom_call" in text
