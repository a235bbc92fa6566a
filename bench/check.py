"""What decides ``correct``: the timed path's own outputs, held to the
plain reference.

``Recorder`` wraps the engine's ``dispatch`` and ``alloc_slots`` for
the whole run. It keeps, for every prefill batch dispatched inside the
window, the prompts and the device array of the served next tokens;
for every decode step, which arena rows were active, the token each
consumed and its position, so each leased row's token history can be
rebuilt; and, for a seeded sample of the window's decode steps plus
the window's last one (the longest histories), the served logits.
Nothing it does reads the device inside the window.

``evaluate`` runs once the window has closed and the program's arena
is freed. It samples served prefill answers from the seed, runs the
reference over each prompt and over each sampled decode row's whole
history, and reports the widest gap by which a served token's
reference logit lies below the reference's best (``model.gap``).
"""
from __future__ import annotations

import math
import random
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import model as M

PREFILL_SAMPLE = 96     # served prompts compared per run
PREFILL_BLOCK = 8       # prompts per reference call
DECODE_SAMPLE = 64      # decode steps whose logits are kept per run


@dataclass
class PrefillBatch:
    t: float
    seq: int
    payload: Optional[list]  # the prompts, one per real row
    batch: int               # real rows
    bucket: int              # rows the program ran
    outputs: object          # device array of served next tokens


@dataclass
class DecodeStep:
    t: float
    rows: List[Tuple[int, int, int]]  # (arena row, lease, position)
    logits: Optional[object] = None   # device array, kept steps only


@dataclass
class Recorder:
    engine: object
    loop: object
    seed: int
    expected_steps: float = 1.0       # decode steps the window should run
    annotate: bool = False            # profiler spans around each call
    window: Tuple[float, float] = (math.inf, math.inf)
    prefill: List[PrefillBatch] = field(default_factory=list)
    decode: List[DecodeStep] = field(default_factory=list)
    history: Dict[int, List[int]] = field(default_factory=dict)
    row_lease: Dict[int, int] = field(default_factory=dict)
    overflow: int = 0                  # positions past the arena's end

    def __post_init__(self):
        self._dispatch = self.engine.dispatch
        self._alloc = self.engine.alloc_slots
        self.engine.dispatch = self.dispatch
        self.engine.alloc_slots = self.alloc_slots
        self._rng = random.Random(f"check-{self.seed}")
        self._last: Optional[DecodeStep] = None
        self._last_logits = None

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def alloc_slots(self, mid, seq, n, start_pos=0):
        rows = self._alloc(mid, seq, n, start_pos)
        for r in rows:
            lease = len(self.history)
            self.history[lease] = [0] * start_pos
            self.row_lease[int(r)] = lease
        return rows

    def dispatch(self, mid, shape_key, batch_size, kind="prefill",
                 slots=None, payload=None, step_rows=None):
        t = self.loop.now
        span = (jax.profiler.TraceAnnotation(f"bench.dispatch.{kind}")
                if self.annotate else nullcontext())
        with span:
            handle = self._dispatch(mid, shape_key, batch_size, kind,
                                    slots=slots, payload=payload,
                                    step_rows=step_rows)
        inside = self.in_window(t)
        if kind == "prefill":
            if inside:
                self.prefill.append(PrefillBatch(
                    t, shape_key[0], payload, batch_size,
                    handle.bucket_batch, handle.outputs))
            return handle
        if slots is None:
            return handle  # prefix mode: no leased rows to follow
        # A window can hold two frames of one stream: its row still
        # steps once (the engine masks rows, it does not count them).
        active = sorted({int(r) for r in (slots if step_rows is None else step_rows)})
        tokens = payload if isinstance(payload, dict) else {}
        seq = shape_key[0]
        rows = []
        for r in active:
            lease = self.row_lease[r]
            hist = self.history[lease]
            pos = len(hist)
            if pos >= seq:
                self.overflow += 1
            hist.append(int(tokens.get(r, 0)))
            rows.append((r, lease, pos))
        if inside and rows:
            step = DecodeStep(t, rows)
            keep_p = min(1.0, DECODE_SAMPLE / max(self.expected_steps, 1.0))
            if self._rng.random() < keep_p:
                step.logits = handle.outputs
            self._last = step
            self._last_logits = handle.outputs
            self.decode.append(step)
        return handle

    def close(self) -> None:
        """Keep the window's last decode step, then stop recording."""
        if self._last is not None and self._last.logits is None:
            self._last.logits = self._last_logits
        self._last_logits = None
        self.engine.dispatch = self._dispatch
        self.engine.alloc_slots = self._alloc


def evaluate(rec: Recorder, shape: M.Shape, weights, seed: int,
             quant: bool = False) -> Dict[str, Optional[float]]:
    """Widest reference-logit gaps of the window's served tokens.

    ``quant=True`` puts the float8 control in the program's place: the
    token it ranks first at each sampled position is judged instead of
    the served one (the calibration's upper reading)."""
    rng = random.Random(f"sample-{seed}")
    out: Dict[str, Optional[float]] = {
        "prefill_gap": None, "prefill_compared": 0,
        "decode_gap": None, "decode_compared": 0,
    }
    rows = []
    for pb in rec.prefill:
        served = np.asarray(pb.outputs)[:pb.batch]
        for i in range(pb.batch):
            prompt = None if pb.payload is None else pb.payload[i]
            if prompt is not None:
                rows.append((np.asarray(prompt, np.int32), int(served[i])))
    if rows:
        pick = sorted(rng.sample(range(len(rows)), min(PREFILL_SAMPLE, len(rows))))
        worst = 0.0
        for b in range(0, len(pick), PREFILL_BLOCK):
            block = [rows[i] for i in pick[b:b + PREFILL_BLOCK]]
            n = len(block)
            # Whole blocks only, so the reference's shapes repeat.
            toks = np.stack([p for p, _ in block] + [block[0][0]] * (PREFILL_BLOCK - n))
            last = np.full((PREFILL_BLOCK, 1), toks.shape[1] - 1, np.int32)
            ref = M.reference_logits(shape, weights, toks, last)[:n, 0]
            if quant:
                ctl = M.reference_logits(shape, weights, toks, last, True)[:n, 0]
                served = ctl.argmax(-1)
            else:
                served = np.array([t for _, t in block])
            worst = max(worst, float(M.gap(ref, served).max()))
        out["prefill_gap"], out["prefill_compared"] = worst, len(pick)
    kept = [s for s in rec.decode if s.logits is not None]
    if kept:
        want: Dict[int, List[Tuple[int, int]]] = {}  # lease -> (pos, served)
        for step in kept:
            served = np.asarray(step.logits).argmax(-1)
            for r, lease, pos in step.rows:
                want.setdefault(lease, []).append((pos, int(served[r])))
        worst, n = 0.0, 0
        for lease, items in sorted(want.items()):
            hist = rec.history[lease]
            length = max(p for p, _ in items) + 1
            toks = np.zeros((1, M.pad_len(length)), np.int32)
            toks[0, :length] = hist[:length]
            pos = np.array([[p for p, _ in items]], np.int32)
            ref = M.reference_logits(shape, weights, toks, pos)[0]
            if quant:
                served = M.reference_logits(shape, weights, toks, pos, True)[0].argmax(-1)
            else:
                served = np.array([t for _, t in items])
            worst = max(worst, float(M.gap(ref, served).max()))
            n += len(items)
        out["decode_gap"], out["decode_compared"] = worst, n
    return out
