#!/usr/bin/env python3
"""Chip smoke: serve granite-3-2b at published widths on a TPU.

Default (one chip): build and profile the live path (SimLink transport
-> gateway -> admission -> DisBatcher/EDF -> slot arena) for
granite-3-2b in bf16, serve a few hundred seeded frames, then check one
arena row's logits against a batch-1 reference and one Pallas decode
step against the XLA path. ``--multi-chip``: four slices on four chips
only — each slice's params and arena on its own chip, the same token's
logits on every slice against the one-chip reference, and a slice
failed under load (conservation, zero survivor recompiles, re-homed
tails complete).

The last stdout line is ``{"ok": ..., "device": {...}}``; the exit code
is 0 only when every check held. With no TPU it exits non-zero before
printing any result.

    python3 chip_smoke.py
    python3 chip_smoke.py --multi-chip
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.launch import checks  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build, conserved, serve  # noqa: E402

ARCH = "granite-3-2b"
TOKEN = 1234  # fed to both sides of a logit check (mod the vocab)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit's retrieval time counts as compile
    time, so a warm cache shows as fewer seconds)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self):
        out = (self.seconds, self.cache_hits)
        self.seconds, self.cache_hits = 0.0, 0
        return out


def log(msg):
    print(msg, flush=True)


def nbytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def wcets(table):
    """Profiled step times: prefill per batch bucket, decode flat."""
    out = {f"prefill{k[1]}": v for k, v in table.entries.items()}
    out.update({f"decode{k[1]}": t for k, (_, t) in table.flat_entries.items()})
    return json.dumps(out)


def peak_bytes(dev):
    return dev.memory_stats()["peak_bytes_in_use"]


def one_chip(cfg, clock):
    dev = jax.devices()[0]
    stack = build(cfg)
    secs, hits = clock.lap()
    log(f"build: {stack.build_seconds:.1f}s (compile {secs:.1f}s, "
        f"{hits} cache hits; the rest is engine init + §4.1 profiling)")
    (sl,) = stack.slices.values()
    log(f"profiled WCETs (s, p99 of 5 runs, host clock around "
        f"block_until_ready): {wcets(sl.spec.table)}")
    served = serve(stack)
    c = served.counts
    log(f"served {served.serve_seconds:.1f}s: {json.dumps(c)}")
    log(f"device busy (host clock, AsyncDevice): {sl.device.busy_time:.3f}s "
        f"of {served.serve_seconds:.3f}s served")
    eng = sl.engine
    log(f"prefill programs: {eng.stats['prefill_tail_one_row']} with the "
        f"last block on one row, {eng.stats['prefill_unembed_only']} with "
        f"only the unembedding cut")
    mid, seq = cfg.arch_id, stack.traffic.decode_seq
    params_b = nbytes(eng.params)
    arena_b = eng.arena_nbytes(mid, seq)
    peak = peak_bytes(dev)
    log(f"memory: peak_bytes_in_use {peak} vs params {params_b} + arena "
        f"{arena_b} = {params_b + arena_b} (excess {peak - params_b - arena_b})")

    tok = TOKEN % cfg.vocab_size
    got = checks.arena_row_logits(eng, mid, seq, tok)
    ref = checks.reference_logits(eng, mid, seq, tok)
    row = checks.compare_logits(got, ref, checks.LOGIT_RTOL[cfg.param_dtype])
    log(f"arena row vs batch-1 reference: {json.dumps(row)}")
    kern = checks.pallas_vs_xla(eng, mid, seq)
    log(f"pallas decode_attention vs f32 oracle (xla path beside it): "
        f"{json.dumps(kern['attention'])}")
    log(f"pallas vs xla decode step: {json.dumps(kern['step'])}")
    secs, hits = clock.lap()
    log(f"checks compile {secs:.1f}s ({hits} cache hits)")

    verdicts = {
        "completed > 0": c["completed"] > 0,
        "conserved": conserved(c),
        "every sent frame ingested once": c["ingested"] == c["frames_sent"],
        "lost == 0": c["lost"] == 0,
        "zero decode recompiles": c["decode_compiles"] == 0,
        "no health transitions": c["health_transitions"] == 0,
        "wire conserved": c["wire_conserved"] == 1 and c["malformed"] == 0,
        "arena row within tolerance": row["ok"],
        "pallas attention within tolerance": kern["attention"]["ok"],
        "pallas decode step within tolerance": kern["step"]["ok"],
    }
    return verdicts


def multi_chip(cfg, clock, n=4):
    devs = jax.devices()
    if len(devs) < n:
        log(f"--multi-chip needs {n} devices, found {len(devs)}")
        return {f"{n} devices": False}
    stack = build(cfg, n_slices=n)
    secs, hits = clock.lap()
    log(f"build {n} slices: {stack.build_seconds:.1f}s (compile {secs:.1f}s, "
        f"{hits} cache hits)")
    mid, seq = cfg.arch_id, stack.traffic.decode_seq
    engines = [sl.engine for sl in stack.slices.values()]
    placed = [
        checks.placed_on([e.params, e.arena(mid, seq).cache], devs[i])
        for i, e in enumerate(engines)
    ]
    log(f"placement: slice i on jax.devices()[i]: {placed}")
    tok = TOKEN % cfg.vocab_size
    ref = checks.reference_logits(engines[0], mid, seq, tok)
    rows = [
        checks.compare_logits(
            checks.arena_row_logits(e, mid, seq, tok), ref,
            checks.LOGIT_RTOL[cfg.param_dtype],
        )
        for e in engines
    ]
    for i, r in enumerate(rows):
        log(f"slice{i} arena row vs one-chip reference: {json.dumps(r)}")
    served = serve(stack, fail_at=2.0)
    c = served.counts
    log(f"served {served.serve_seconds:.1f}s, failed {served.failed_slice}: "
        f"{json.dumps(c)}")
    for name, sl in stack.slices.items():
        log(f"  {name}: alive={sl.alive} "
            f"decode_compiles={sl.engine.stats['decode_compiles']} "
            f"completed={sl.scheduler.metrics.completed_frames} "
            f"peak_bytes_in_use={peak_bytes(sl.engine.device)}")
    return {
        "params and arena on own chip": all(placed),
        "logits match one-chip reference": all(r["ok"] for r in rows),
        "conserved": conserved(c),
        "zero survivor decode recompiles": c["decode_compiles"] == 0,
        "one operator transition": c["health_transitions"] == 1,
        "re-homed tails complete": (
            c["rehomes"] >= 1 and c["rehomed_completed"] > 0
            and c["parked"] == 0
        ),
        "wire conserved": c["wire_conserved"] == 1 and c["malformed"] == 0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-chip", action="store_true",
                    help="four slices on four chips, with a slice failure")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    n_dev = len(jax.devices())
    log(f"device: {dev.platform} {dev.device_kind} x{n_dev}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    cfg = get_config(ARCH)
    log(f"config: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"vocab={cfg.vocab_size} {cfg.param_dtype} impl={cfg.impl}")
    t0 = time.perf_counter()
    verdicts = multi_chip(cfg, clock) if args.multi_chip else one_chip(cfg, clock)
    for name, ok in verdicts.items():
        log(f"check {'PASS' if ok else 'FAIL'}: {name}")
    ok = all(verdicts.values())
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": ok,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": n_dev,
        },
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
