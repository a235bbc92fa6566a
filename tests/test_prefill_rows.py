"""The prefill program computes only the rows the served token reads.

``Transformer.next_token_logits`` must equal the last row of ``forward``
for every block pattern: a dense attention last block runs its tail on
one row, an MoE or recurrent last block runs whole and only the final
norm and the unembedding are cut. The engine counts which cut each
prefill program it builds takes.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import tiny
from repro.models import model_for
from repro.serving.engine import InferenceEngine, prefill_program

S = 24  # longer than tiny()'s 16-token sliding window

# (case, arch, overrides, the cut next_token_logits takes)
PATTERNS = [
    ("dense-attn", "granite-3-2b", {}, "tail_one_row"),
    # 6-layer swa/attn unit + a (swa, swa) tail: the last layer is a tail
    ("swa-attn-tail", "gemma3-12b", {"n_layers": 8}, "tail_one_row"),
    # two units, no tail: the last layer ends the last scanned superblock
    ("swa-attn-scanned", "gemma3-12b", {"n_layers": 12}, "tail_one_row"),
    ("moe", "mixtral-8x7b", {}, "unembed_only"),
    # (rglru, rglru, swa) unit + an rglru tail
    ("rglru-tail", "recurrentgemma-9b", {"n_layers": 4}, "unembed_only"),
    ("rwkv", "rwkv6-1.6b", {}, "unembed_only"),
]


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize(
    "arch,overrides,cut", [p[1:] for p in PATTERNS], ids=[p[0] for p in PATTERNS]
)
def test_next_token_logits_match_forward(arch, overrides, cut, batch):
    cfg = tiny(arch, **overrides)
    model = model_for(cfg)
    assert model.prefill_cut == cut
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(batch), (batch, S), 0, cfg.vocab_size
    )
    want = model.forward(params, tokens)[0][:, -1]
    got = jax.jit(model.next_token_logits)(params, tokens)
    assert got.shape == (batch, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    served = prefill_program(model)(params, tokens)
    assert served.tolist() == want.argmax(-1).tolist()


def test_next_token_logits_match_forward_mrope():
    """mrope (qwen2-vl): the one-row query takes the last of each of the
    three position streams."""
    cfg = tiny("qwen2-vl-72b")
    model = model_for(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab_size)
    pos = jnp.arange(S)[None, None, :] * jnp.array([1, 2, 3])[:, None, None]
    pos = jnp.broadcast_to(pos, (3, 2, S))
    want = model.forward(params, tokens, pos)[0][:, -1]
    got = jax.jit(model.next_token_logits)(params, tokens, pos)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_engine_counts_prefill_programs_by_cut():
    configs = {case: tiny(arch, **ov) for case, arch, ov, _ in PATTERNS}
    engine = InferenceEngine(configs)
    for case in configs:
        engine.warmup(case, (S,), [1, 1])  # built once per (model, seq, batch)
    one_row = sum(cut == "tail_one_row" for *_, cut in PATTERNS)
    want = {
        "prefill_tail_one_row": one_row,
        "prefill_unembed_only": len(PATTERNS) - one_row,
    }
    assert {k: engine.stats[k] for k in want} == want
    # They describe the programs the engine holds: a reset keeps them.
    engine.reset_stats()
    assert {k: engine.stats[k] for k in want} == want
    assert engine.stats["prefill_compiles"] == 0
