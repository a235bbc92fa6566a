"""Serving launcher: the live serving path, end to end.

``build`` assembles the normal path with ``build_live_transport`` —
SimLink transport -> ingest gateway -> admission -> DisBatcher/EDF ->
slot arena — for one model with one prefill and one decode category,
and profiles it (paper §4.1). ``serve`` registers seeded periodic
streams, serves them on a wall clock and returns the counts.
``chip_smoke.py`` runs the same two functions at published widths on a
TPU.

  PYTHONPATH=src python -m repro.launch.serve            # published widths
  PYTHONPATH=src python -m repro.launch.serve --tiny     # reduced, CPU
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass
from typing import Dict, Optional

import jax

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config, tiny
from repro.core import Category
from repro.ingest import PeriodicSource, SimLink, TransportSource
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.batcher_bridge import build_live_transport


@dataclass(frozen=True)
class Traffic:
    """The seeded stream mix ``serve`` registers.

    Decode streams send one token per frame into their leased arena row;
    prefill streams send whole ``prefill_seq``-token prompts. Every
    stream's deadline is twice its period, so a DisBatcher window
    (deadline / 2) holds at most one frame per stream and each decode
    step stages one token per row.
    """

    prefill_seq: int = 512
    decode_seq: int = 2048
    decode_streams: int = 4
    decode_period: float = 0.1
    decode_frames: int = 60
    prefill_streams: int = 2
    prefill_period: float = 0.5
    prefill_frames: int = 12

    def streams(self):
        """(seq, payload shape, period, frames) per stream, decode first."""
        dec = (self.decode_seq, (), self.decode_period, self.decode_frames)
        pre = (
            self.prefill_seq, (self.prefill_seq,), self.prefill_period,
            self.prefill_frames,
        )
        return [dec] * self.decode_streams + [pre] * self.prefill_streams


@dataclass
class Stack:
    """The built serving stack: cluster, slices (engines + arenas) and
    transport, compiled and profiled, with no stream registered yet."""

    cfg: ModelConfig
    traffic: Traffic
    cluster: object
    slices: dict
    transport: object
    build_seconds: float  # engine build + compile + §4.1 profiling


@dataclass
class Served:
    counts: Dict[str, float]
    serve_seconds: float  # wall clock of the served window
    failed_slice: Optional[str] = None


def build(
    cfg: ModelConfig,
    traffic: Traffic = Traffic(),
    n_slices: int = 1,
    profile_runs: int = 5,
) -> Stack:
    """Build, compile and profile the live path for ``cfg``: one prefill
    and one decode category, ``n_slices`` slices on one wall clock
    (slice i on ``jax.devices()[i % n]``)."""
    mid = cfg.arch_id
    cats = [
        (mid, (traffic.prefill_seq,), "prefill"),
        (mid, (traffic.decode_seq,), "decode"),
    ]
    t0 = time.perf_counter()
    cluster, slices, _gateway, transport, _binding = build_live_transport(
        {mid: cfg}, cats,
        slice_names=tuple(f"slice{i}" for i in range(n_slices)),
        profile_runs=profile_runs,
    )
    return Stack(
        cfg=cfg, traffic=traffic, cluster=cluster, slices=slices,
        transport=transport, build_seconds=time.perf_counter() - t0,
    )


def serve(
    stack: Stack, fail_at: Optional[float] = None, seed: int = 0
) -> Served:
    """Register the seeded streams, serve them to the end, return counts.

    ``fail_at`` (seconds into the served window) fail-stops the home
    slice of the first admitted stream, so its tail re-homes onto a
    survivor under load.
    """
    cfg, traffic = stack.cfg, stack.traffic
    cluster, slices, transport = stack.cluster, stack.slices, stack.transport
    mid = cfg.arch_id
    loop = cluster.loop
    clients = []
    for i, (seq, shape, period, frames) in enumerate(traffic.streams()):
        src = PeriodicSource(
            period=period, n_frames=frames, payload_shape=shape,
            vocab=cfg.vocab_size, seed=seed * 1000 + i,
        )
        client = TransportSource(
            src, Category(mid, (seq,)), 2 * period,
            SimLink(loop, transport.datagram),
        )
        client.start(transport)
        clients.append(client)
    failed = None
    if fail_at is not None:
        victim = next(
            ts for ts in transport.sessions.values()
            if ts.session.state == "active"
        )
        failed = victim.session.slice_name
        loop.schedule(
            loop.now + fail_at, lambda: cluster.fail_slice(failed), priority=0
        )
    # run() returns as soon as every stream has drained; the horizon only
    # bounds a stalled run. finalize_all declares frames still unsent by
    # then lost, so leave a host that lags behind the plan ample room.
    duration = max(p * f for _s, _sh, p, f in traffic.streams())
    horizon = 4 * duration + 10.0
    t0 = time.perf_counter()
    try:
        cluster.run(until=loop.now + horizon)
        transport.finalize_all()
        cluster.run(until=loop.now + 1.0)
    finally:
        for sl in slices.values():
            sl.device.close()
    serve_seconds = time.perf_counter() - t0

    agg = cluster.aggregate_metrics()
    metrics = [sl.scheduler.metrics for sl in slices.values()]
    tails = {t for t in cluster.failover_map.values() if t is not None}
    counts = {
        "streams": len(clients),
        "admitted": sum(c.state != "rejected" for c in clients),
        "frames_sent": sum(c.frames_sent for c in clients),
        "ingested": agg["ingested_frames"],
        "completed": agg["completed_frames"],
        "missed": agg["missed_frames"],
        "e2e_p50_s": agg["e2e_p50"],
        "e2e_p99_s": agg["e2e_p99"],
        "dropped": agg["dropped_frames"],
        "lost": agg["lost_frames"],
        "decode_compiles": sum(
            sl.engine.stats["decode_compiles"] for sl in slices.values()
        ),
        "prefill_compiles": sum(
            sl.engine.stats["prefill_compiles"] for sl in slices.values()
        ),
        "health_transitions": len(cluster.health.transitions),
        "rehomes": sum(ts.rehomes for ts in transport.sessions.values()),
        "rehomed_completed": sum(
            rid in tails for m in metrics for rid, _idx in m.frame_records
        ),
        "parked": agg["parked"],
        "payload_collisions": sum(m.payload_collisions for m in metrics),
        "malformed": transport.malformed,
        "wire_conserved": int(
            all(ts.wire_conserved() for ts in transport.sessions.values())
        ),
    }
    return Served(counts=counts, serve_seconds=serve_seconds,
                  failed_slice=failed)


def conserved(counts: Dict[str, int]) -> bool:
    """``completed + dropped + lost == ingested`` (the cluster identity)."""
    return (
        counts["completed"] + counts["dropped"] + counts["lost"]
        == counts["ingested"]
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU runs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not args.tiny and jax.devices()[0].platform == "cpu":
        raise SystemExit("published widths need an accelerator; use --tiny")
    enable_compile_cache()
    cfg = tiny(args.arch) if args.tiny else get_config(args.arch)
    stack = build(cfg)
    served = serve(stack, seed=args.seed)
    print(f"build (compile + profile) {stack.build_seconds:.1f}s, "
          f"served {served.serve_seconds:.1f}s")
    print(json.dumps(served.counts))


if __name__ == "__main__":
    main()
