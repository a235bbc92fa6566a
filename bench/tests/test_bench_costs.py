"""The benchmark's FLOP and byte counts against hand counts from the
published shapes, and the reference's gap arithmetic."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import model as M

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shape(name):
    return M.Shape.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_granite_block_and_weight_bytes():
    s = shape("granite-3-2b")
    # q 2048x32x64, k and v 2048x8x64 each, o 32x64x2048, SwiGLU 3x2048x8192
    per_block = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192
    assert M.block_matmul_params(s) == per_block == 60_817_408
    params = 40 * (per_block + 2 * 2048) + 49155 * 2048 + 2048
    assert M.param_bytes(s) == 2 * params == 5_067_063_296  # 5.07 GB, as served


def test_phi4_weight_and_kv_bytes():
    s = shape("phi4-mini-3.8b")
    per_block = 3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072 + 3 * 3072 * 8192
    assert M.block_matmul_params(s) == per_block
    assert M.param_bytes(s) == 2 * (32 * (per_block + 2 * 3072)
                                     + 200064 * 3072 + 3072)
    # K and V, 32 layers, 8 heads of 128, bf16: 131,072 bytes a position
    assert M.kv_bytes(s, 1) == 131_072
    assert M.kv_bytes(s, 2048) * 8 == 2_147_483_648  # the arena


def test_token_and_prefill_flops():
    s = shape("granite-3-2b")
    mm = 40 * M.block_matmul_params(s) + 49155 * 2048
    assert M.token_flops(s, 1) == 2 * mm + 40 * 4 * 32 * 64
    assert M.token_flops(s, 100) - M.token_flops(s, 1) == 40 * 4 * 32 * 64 * 99
    # a causal prompt: position p attends to p + 1 keys; logits at the end
    n = 512
    one = 2 * 40 * M.block_matmul_params(s) * n \
        + sum(40 * 4 * 32 * 64 * (p + 1) for p in range(n)) + 2 * 49155 * 2048
    assert M.prefill_flops(s, 1, n) == one
    assert M.prefill_flops(s, 8, n) == 8 * one


def test_decode_step_bytes_and_least_time():
    s = shape("phi4-mini-3.8b")
    ctx = [100, 200]
    want = M.param_bytes(s) + M.kv_bytes(s, 300) + 2 * M.kv_bytes(s, 1)
    assert M.decode_step_bytes(s, ctx) == want
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = M.least_seconds(M.decode_step_flops(s, ctx), want, peak)
    assert least == pytest.approx(want / 819e9)  # decode is memory-bound
    g = shape("granite-3-2b")
    f = M.prefill_flops(g, 8, 512)
    assert M.least_seconds(f, M.prefill_bytes(g, 8, 512), peak) == f / 197e12


def test_gap_and_padding():
    ref = np.array([[0.0, 2.0, 1.5], [3.0, -1.0, 2.5]])
    assert M.gap(ref, np.array([1, 2])).tolist() == [0.0, 0.5]
    assert [M.pad_len(n) for n in (1, 128, 129, 700, 2048)] == \
        [128, 128, 256, 1024, 2048]
