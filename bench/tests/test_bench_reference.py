"""The reference against the program's own forward pass, at a tiny
width in float32 on the CPU, on the benchmark's seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as M


@pytest.fixture(scope="module")
def tiny():
    from repro.configs.registry import tiny as tiny_cfg
    from repro.models import model_for

    cfg = tiny_cfg("phi4-mini-3.8b")
    s = M.Shape(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size,
                cfg.rope_theta, 1e-6, "float32")
    w = M.make_weights(s, 2**40 + 3, jax.devices()[0])
    return s, w, model_for(cfg)


def test_layout_matches_program(tiny):
    s, w, model = tiny
    prog = model.abstract_params(jnp.float32)
    assert jax.tree.structure(prog) == jax.tree.structure(w)
    assert [a.shape for a in jax.tree.leaves(prog)] == \
        [a.shape for a in jax.tree.leaves(w)]


def test_weights_follow_the_seed(tiny):
    s, w, _ = tiny
    again = M.make_weights(s, 2**40 + 3, jax.devices()[0])
    other = M.make_weights(s, 2**40 + 4, jax.devices()[0])
    assert np.array_equal(w["embed"], again["embed"])
    assert not np.array_equal(w["embed"], other["embed"])


def test_reference_equals_program_forward(tiny):
    s, w, model = tiny
    toks = np.random.default_rng(1).integers(0, s.vocab, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(w, jnp.asarray(toks))
    pos = np.array([[47, 3], [10, 47]], np.int32)
    ref = M.reference_logits(s, w, toks, pos)
    want = np.take_along_axis(np.asarray(logits), pos[..., None], axis=1)
    np.testing.assert_allclose(ref, want, rtol=1e-5, atol=1e-5)
    assert M.gap(ref[:, 0], ref[:, 0].argmax(-1)).max() == 0.0


def test_float8_control_departs(tiny):
    s, w, _ = tiny
    toks = np.random.default_rng(2).integers(0, s.vocab, (4, 64)).astype(np.int32)
    pos = np.tile(np.arange(0, 64, 4, dtype=np.int32), (4, 1))
    ref = M.reference_logits(s, w, toks, pos)
    ctl = M.reference_logits(s, w, toks, pos, quant=True)
    assert np.abs(ctl - ref).max() > 1e-2
    assert M.gap(ref, ctl.argmax(-1)).max() > 0.0
