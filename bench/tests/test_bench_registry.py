"""Every configuration, traffic mix and metric that BENCHMARK.json names
is found by name, and the file keeps to the benchmark's contract."""
import json
import re
from pathlib import Path

import pytest

from bench import harness
from bench import model as M
from bench.tests.rehearse import BENCHMARK, DECODE_SPEC
from bench.traffic import generator as G

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads(BENCHMARK.read_text())
DECODE = json.loads(DECODE_SPEC.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [(w["name"], path) for path, spec in ((BENCHMARK, SPEC), (DECODE_SPEC, DECODE))
         for w in spec["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / SPEC["command"][1]).is_file()


@pytest.mark.parametrize("cell, spec", CELLS, ids=[c for c, _ in CELLS])
def test_cell_resolves(cell, spec):
    c = harness.load_cell(cell, spec)
    assert c.chips == 1
    kinds = {g["kind"] for g in c.mix["streams"]}
    assert kinds <= set(G.KINDS)
    assert {f"{k}_gap" for k in kinds} <= set(c.limits)
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)


def test_names_units_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    # every configuration has a cell; a metric names only cells that exist
    assert {c["name"] for c in SPEC["configs"]} == {w["config"] for w in SPEC["workloads"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert all(set(m.get("workloads", cells)) <= cells for m in metrics)


@pytest.mark.parametrize("conf", SPEC["configs"] + DECODE["configs"],
                         ids=lambda c: c["name"])
def test_config_file_matches_program(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    s = M.Shape.from_config(data)
    cfg = harness.program_config(data)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff, s.vocab)
    from repro.configs.registry import get_config

    pub = get_config(data["program_arch"])
    # the file changes no width of the program's published configuration
    assert (pub.d_model, pub.n_heads, pub.n_kv_heads, pub.resolved_head_dim,
            pub.d_ff, pub.vocab_size, pub.n_layers) == \
        (s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff, s.vocab, s.layers)


def test_plan_is_fixed_by_the_seed():
    mix = G.load_mix("prefill-camera")
    g = mix["streams"][0]
    a = G.plan(mix, 2**33 + 1, 10.0)
    b = G.plan(mix, 2**33 + 1, 10.0)
    c = G.plan(mix, 2**33 + 2, 10.0)
    assert [(s.start, s.offsets, s.seed) for s in a] == \
        [(s.start, s.offsets, s.seed) for s in b]
    # another seed: the same streams and rates, frames at other times
    assert [s.start for s in a] != [s.start for s in c]
    assert len(a) == len(c) == g["count"]
    half = g["jitter_frac"] * g["period_s"] / 2
    for s, t in zip(a, c):
        assert 0.0 <= s.start < g["period_s"]
        assert all(x < y for x, y in zip(s.offsets, s.offsets[1:]))
        assert all(abs(x - i * g["period_s"]) <= half
                   for i, x in enumerate(s.offsets[1:], 1))
        assert (s.kind, s.tokens, s.period, s.deadline) == \
            (t.kind, t.tokens, t.period, t.deadline)
        src = G.PlannedSource(s, 100)
        assert (src.payload(3) == G.PlannedSource(s, 100).payload(3)).all()
        assert src.payload(3).shape == (512,)
        assert (src.payload(3) != G.PlannedSource(t, 100).payload(3)).any()
