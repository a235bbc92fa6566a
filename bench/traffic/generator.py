"""The one traffic generator: open-loop seeded streams from a mix file.

A mix file (``bench/traffic/<name>.json``) holds only numbers: the
warm-up, and groups of streams, each with its kind (``prefill``: one
``tokens``-long prompt per frame; ``decode``: one token per frame into
a leased arena row of ``arena_tokens``), count, period, relative
deadline and jitter. ``plan`` draws everything from the seed: each
stream's phase, uniform over one period (the moment its camera
started), its jittered frame offsets, and its tokens. Every seed offers
the same streams, rates and prompt lengths; the seed moves when their
frames fall.

Frame offsets are the program's ``CameraSource`` rule (frame i at
``i * period`` plus uniform jitter of at most ``jitter_frac * period / 2``
either way, which never reorders frames). ``OpenLoopClient`` sends them
through the program's ``TransportSource`` over a ``SimLink``, with one
change: each frame is scheduled at its absolute due time, so a late
send does not delay the frames after it, and client flow control is
off, so the server cannot slow the offered load.
"""
from __future__ import annotations

import json
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

from repro.ingest.sources import FrameSource
from repro.ingest.transport import (
    FIN,
    TransportSource,
    encode_control,
    encode_data,
)

MIX_DIR = Path(__file__).resolve().parent
KINDS = ("prefill", "decode")


def load_mix(name: str) -> Dict:
    mix = json.loads((MIX_DIR / f"{name}.json").read_text())
    for g in mix["streams"]:
        if g["kind"] not in KINDS:
            raise ValueError(f"{name}: unknown stream kind {g['kind']!r}")
    return mix


@dataclass
class Stream:
    """One planned client stream; times are seconds from traffic start."""

    group: int
    kind: str
    tokens: int        # prefill prompt length (decode: the arena length)
    period: float
    deadline: float
    start: float       # phase of frame 0
    offsets: List[float]  # frame offsets from ``start``
    seed: int


def _offsets(rng: random.Random, period: float, jitter: float, start: float,
             horizon: float) -> List[float]:
    half = jitter * period / 2.0
    out = []
    i = 0
    while start + i * period < horizon:
        out.append(max(0.0, i * period + rng.uniform(-half, half)))
        i += 1
    return out


def plan(mix: Dict, seed: int, horizon: float) -> List[Stream]:
    """Every stream of ``mix`` for one run of ``horizon`` seconds of
    traffic (warm-up and window together)."""
    streams: List[Stream] = []
    for gi, g in enumerate(mix["streams"]):
        period, n = float(g["period_s"]), int(g["count"])
        tokens = int(g["tokens"] if g["kind"] == "prefill"
                     else mix["arena_tokens"])
        jitter = float(g.get("jitter_frac", 0.0))
        for j in range(n):
            rng = random.Random(f"bench-{seed}-{gi}-{j}")
            start = rng.uniform(0.0, period)
            streams.append(Stream(
                group=gi, kind=g["kind"], tokens=tokens, period=period,
                deadline=float(g["deadline_s"]), start=start,
                offsets=_offsets(rng, period, jitter, start, horizon),
                seed=rng.getrandbits(63),
            ))
    return streams


class PlannedSource(FrameSource):
    """A ``FrameSource`` over a planned stream: its offsets, and tokens
    drawn from the stream's seed and the frame index."""

    def __init__(self, stream: Stream, vocab: int):
        shape = (stream.tokens,) if stream.kind == "prefill" else ()
        super().__init__(stream.period, len(stream.offsets), shape, vocab,
                         stream.seed)
        self.stream = stream

    def _offsets(self) -> List[float]:
        return list(self.stream.offsets)

    def payload(self, index: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, index))
        return rng.integers(0, self.vocab, size=self.payload_shape,
                            dtype=np.int32)


class OpenLoopClient(TransportSource):
    """``TransportSource`` that sends each frame at its absolute due
    time and records how late each send ran (``lateness``, seconds).

    ``start`` only opens the session (admission); ``arm`` sets the
    traffic's start and schedules the first frame."""

    def __init__(self, source: PlannedSource, category, link):
        super().__init__(source, category, source.stream.deadline, link,
                         flow_control=False)
        self.t0 = 0.0
        self.lateness: List[float] = []

    def arm(self, t0: float) -> None:
        self.t0 = t0  # a refused stream's frames are still due (and missed)
        if self.state == "active":
            self._at(self.plan[0].offset)

    def _at(self, offset: float) -> None:
        self.loop.schedule(self.t0 + offset, self._send_frame,
                           priority=getattr(self.loop, "PRIO_ARRIVAL", 0))

    def _send_next(self) -> None:
        """The first send that the base class schedules at HELLO: frames
        go out on the schedule ``arm`` sets instead."""

    def _send_frame(self) -> None:
        if self.state != "active":
            return
        k = self._cursor
        now = self.loop.now
        self.lateness.append(now - (self.t0 + self.plan[k].offset))
        payload = self.plan[k].payload
        self._remember(k, payload)
        self.frames_sent += 1
        self.link.send(encode_data(self.sid, k, now, payload))
        self._cursor += 1
        if self._cursor < len(self.plan):
            self._at(self.plan[self._cursor].offset)
            return
        self.state = "done"
        self.link.send(
            encode_control(FIN, {"sid": self.sid, "total": len(self.plan)}),
            chaos=False,
        )
