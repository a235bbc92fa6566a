"""The trace reducer: on a hand-made trace, and on a short trace of the
decode cell recorded on a TPU v5e and committed beside this file."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_trace():
    ev = {
        "ops": [("a", 100, 50), ("b", 120, 60), ("a", 300, 100), ("c", 500, 100)],
        "modules": [("jit_run", 100, 80), ("jit_run", 300, 100),
                    ("jit_other", 500, 100)],
        "host": [("bench.window", 150, 400), ("bench.dispatch.decode", 250, 40),
                 ("bench.dispatch.decode", 420, 60)],
    }
    red = TR.reduce(ev, TR.window_of(ev))
    assert red["window_ns"] == 400
    # ops clipped to [150, 550): 150-180, 300-400, 500-550
    assert red["busy_ns"] == 30 + 100 + 50
    assert red["programs"] == {"jit_run": [1, 100], "jit_other": [1, 100]}
    assert TR.program_time(red, "tpu") == (1, 100)
    assert dict(red["device_ops"]) == {"a": 100, "b": 30, "c": 50}
    # gaps: 180-300 (dispatch span covers 250-290), 400-500 (420-480)
    # 180-300: the dispatch span covers 40 of 120; 400-500: 60 of 100
    assert red["idle_gaps"] == [("no_bench_span", 120),
                                ("bench.dispatch.decode", 100)]
    assert red["idle_by_host"] == {"bench.dispatch.decode": 100,
                                   "no_bench_span": 120}
    assert red["busy_ns"] + sum(ns for _, ns in red["idle_gaps"]) == 400


def _recorded():
    path = DATA / "trace_decode.json.gz"
    if not path.exists():
        pytest.fail(f"missing {path}")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _busy_by_grid(ev, lo, hi, step=1000):
    """Busy time counted on a 1 us grid: an independent union."""
    import numpy as np

    grid = np.zeros((hi - lo) // step + 1, bool)
    for _n, s, d in ev["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo + step - 1) // step] = True
    return int(grid.sum()) * step


def test_recorded_decode_trace():
    ev = _recorded()
    assert ev["platform"] == "tpu"
    lo, hi = TR.window_of(ev)
    red = TR.reduce(ev, (lo, hi))
    # one second of the phi4-mini decode cell: 21 decode steps of ~25 ms
    assert TR.program_time(red, "tpu")[0] == 21
    assert abs(red["busy_ns"] - _busy_by_grid(ev, lo, hi)) <= 2000 * len(ev["ops"]) // 100
    names = dict(red["device_ops"])
    # the decode step's layer loop, and its copy of the K/V arena
    assert any(n.startswith("while.") for n in names)
    assert "copy.100 = bf16[32,8,2048,8,128]" in names
    assert 0 < red["busy_ns"] <= red["window_ns"] == hi - lo
    runs, ns = TR.program_time(red, "tpu")
    assert runs > 0 and 0 < ns <= red["window_ns"] + max(d for _, _, d in ev["modules"])
    assert sum(ns for _, ns in red["idle_gaps"]) <= red["window_ns"] - red["busy_ns"]
    total_idle = sum(red["idle_by_host"].values())
    assert total_idle == red["window_ns"] - red["busy_ns"]


def test_op_names():
    assert TR.op_name("%copy.1 = bf16[2,3]{1,0:T(8,128)} copy(%x)") == "copy.1 = bf16[2,3]"
    assert TR.op_name("%while.2 = (s32[], f32[4]) while(%t)") == "while.2 = tuple"
    assert TR.op_name("fusion.7") == "fusion.7"
