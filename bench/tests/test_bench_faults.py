"""Faults planted under the timed path must make ``correct`` false:
a decode step that returns its state unchanged, half of a prefill
batch left out (its rows answered from the other half), and a token
altered where it is produced. (One chip: no exchange between chips.)"""
import jax.numpy as jnp
import pytest

from bench.tests.rehearse import BENCHMARK, DECODE_SPEC, rehearse


def _programs(stack, kind):
    eng = stack.engine
    return [(k, fn) for k, fn in eng._compiled.items() if k[0] == kind]


def decode_state_unchanged(stack):
    for key, fn in _programs(stack, "decode"):
        stack.engine._compiled[key] = (
            lambda p, cache, tok, cur, act, fn=fn:
            (fn(p, cache, tok, cur, act)[0], cache, cur))


def decode_token_altered(stack):
    for key, fn in _programs(stack, "decode"):
        def run(p, cache, tok, cur, act, fn=fn):
            logits, c, n = fn(p, cache, tok, cur, act)
            return jnp.roll(logits, 1, axis=-1), c, n
        stack.engine._compiled[key] = run


def prefill_half_batch(stack):
    """The second half of each batch's real rows (the whole of a batch
    of one) is left out: those rows are answered as empty prompts."""
    engine = stack.engine
    dispatch = engine.dispatch

    def run(mid, shape_key, batch_size, kind="prefill", payload=None, **kw):
        if kind == "prefill" and payload is not None:
            payload = list(payload)
            for i in range(batch_size // 2, batch_size):
                payload[i] = 0 * payload[i]
        return dispatch(mid, shape_key, batch_size, kind, payload=payload, **kw)

    engine.dispatch = run


def prefill_token_altered(stack):
    vocab = stack.shape.vocab
    for key, fn in _programs(stack, "prefill"):
        stack.engine._compiled[key] = (
            lambda p, tokens, fn=fn: (fn(p, tokens) + 1) % vocab)


@pytest.mark.parametrize("cell, spec, fault, check", [
    ("phi4-decode-streams", DECODE_SPEC, decode_state_unchanged, "decode_gap"),
    ("phi4-decode-streams", DECODE_SPEC, decode_token_altered, "decode_gap"),
    ("granite-prefill-camera", BENCHMARK, prefill_half_batch, "prefill_gap"),
    ("granite-prefill-camera", BENCHMARK, prefill_token_altered, "prefill_gap"),
])
def test_fault_is_caught(cell, spec, fault, check):
    res = rehearse(cell, False, fault=fault, spec=spec)
    assert res["correct"] is False
    c = res["check"][check]
    assert c["value"] is not None and c["value"] > c["limit"]
