"""End-to-end driver: multi-tenant LIVE serving with real JAX execution.

Two reduced LM architectures share one device. The engine compiles
batched prefill steps, the offline profiler (paper §4.1) measures WCETs,
and DeepRT schedules actual jit-compiled executions on a wall clock —
admission control included. Dispatch is asynchronous (zero-stall): the
scheduler loop keeps batching/admitting while XLA executes, and the
footer reports how little host time each job dispatch cost. A
BATCH(Triton-style) baseline runs the same accepted trace for
comparison.

With ``--slices N`` (N > 1) the same workload runs on a LIVE CLUSTER
(``build_live_cluster``): N slices on one wall clock, each owning its
own engine / resident arenas / AsyncDevice / WCET table; placement
routes each request to the lowest-utilization capable slice and
admission on that slice decides finally (spill-on-reject).

With ``--source camera|burst|trace`` the demo streams REAL payload
bytes through the ingest gateway (``repro.ingest``): every frame
carries tokens produced by a jittery camera, a bursty WebRTC-like
source, or a trace replay, deadline-stamped at arrival, staged through
the engine's double-buffered rings, with adaptation-driven load
shedding accounted in the metrics.

With ``--transport`` the cluster additionally sits behind the NETWORK
front door (``repro.ingest.transport``): each stream becomes a
datagram client behind a seed-derived chaotic link (drops, duplicates,
reordering, delay), reassembled in order at the server, with
credit-based backpressure signaled back to the client and session
re-homing armed for slice failover.

    PYTHONPATH=src python examples/serve_multitenant.py [--requests 8]
    PYTHONPATH=src python examples/serve_multitenant.py --slices 2
    PYTHONPATH=src python examples/serve_multitenant.py --slices 2 --source camera
    PYTHONPATH=src python examples/serve_multitenant.py --slices 2 --transport
"""
import argparse
import copy
import json
import sys

from repro.configs.registry import tiny
from repro.core import (
    BATCH,
    Category,
    EventLoop,
    FrameTracer,
    TraceSpec,
    generate_trace,
)
from repro.ingest import (
    BurstSource,
    CameraSource,
    IngestGateway,
    LinkPlan,
    SimLink,
    TraceSource,
    TransportSource,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.batcher_bridge import (
    build_live_cluster,
    build_live_scheduler,
    build_live_transport,
)

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=8)
ap.add_argument("--seq", type=int, default=48)
ap.add_argument("--frames", type=int, default=15)
ap.add_argument("--slices", type=int, default=1,
                help="N > 1 serves through a live multi-slice cluster")
ap.add_argument("--source", choices=("camera", "burst", "trace"), default=None,
                help="stream real payload bytes through the ingest gateway")
ap.add_argument("--transport", action="store_true",
                help="serve through the network front door: chaotic link, "
                     "reassembly, client backpressure (implies a cluster)")
ap.add_argument("--chaos-seed", type=int, default=7,
                help="seed for the per-stream LinkPlan (--transport)")
ap.add_argument("--trace", metavar="PATH", default=None,
                help="dump the frame-lifecycle trace as Chrome "
                     "trace_event JSON (load via chrome://tracing or "
                     "https://ui.perfetto.dev)")
args = ap.parse_args()
enable_compile_cache()

# One tracer spans whatever topology the flags select — wire receive,
# gateway shed verdicts, window closes, EDF dispatch, completions.
TRACER = FrameTracer() if args.trace else None


def dump_trace() -> None:
    if TRACER is None:
        return
    TRACER.dump_chrome_trace(args.trace)
    snap = TRACER.snapshot()
    print(f"trace  : {snap['events']} spans ({snap['emitted']} emitted, "
          f"{snap['evicted']} evicted) -> {args.trace}")

arch_ids = ["granite-3-2b", "rwkv6-1.6b"]
configs = {a: tiny(a) for a in arch_ids}
categories = [(a, (args.seq,), "prefill") for a in arch_ids]


def make_trace():
    spec = TraceSpec(
        mean_period=0.3,
        mean_deadline=0.6,
        n_requests=args.requests,
        frames_per_request=(args.frames, args.frames),
        models=tuple(arch_ids),
        shapes=((args.seq,),),
        seed=3,
    )
    return generate_trace(spec)


def make_sources():
    """One payload-carrying source per request slot (--source mode)."""
    if args.source == "trace":
        spec = TraceSpec(
            mean_period=0.3, mean_deadline=0.6, n_requests=args.requests,
            frames_per_request=(args.frames, args.frames),
            models=tuple(arch_ids), shapes=((args.seq,),), seed=3,
        )
        return [
            (req.category, req.relative_deadline, src)
            for req, src in TraceSource.from_trace(spec, payload_shape=(args.seq,))
        ]
    out = []
    for i in range(args.requests):
        cat = Category(arch_ids[i % len(arch_ids)], (args.seq,))
        if args.source == "camera":
            src = CameraSource(period=0.3, n_frames=args.frames,
                               jitter_frac=0.3, payload_shape=(args.seq,),
                               seed=i)
        else:  # burst: same declared rate, delivered 2x in bursts
            src = BurstSource(period=0.3, n_frames=args.frames, burst=4,
                              duty=0.5, payload_shape=(args.seq,), seed=i)
        out.append((cat, 0.6, src))
    return out


def serve_ingest(target, engines):
    """Stream real payloads through the gateway over ``target`` (a live
    DeepRT or a ClusterScheduler); print the ingest scorecard."""
    gw = IngestGateway(target)
    gw.tracer = TRACER
    sessions = []
    for cat, deadline, src in make_sources():
        s = gw.register(src, cat, relative_deadline=deadline)
        where = f" @{s.slice_name}" if s.slice_name else ""
        print(f"stream {s.request_id} ({cat}): "
              f"{'ADMIT' + where if s.state == 'active' else 'REJECT'}")
        sessions.append(s)
    print(f"\nserving live --source {args.source} "
          f"(payload bytes staged per step, zero-stall)...")
    target.run()
    active = [s for s in sessions if s.state == "active"]
    ingested = sum(s.frames_ingested for s in active)
    delivered = sum(s.frames_delivered for s in active)
    dropped = sum(s.frames_dropped for s in active)
    print(f"ingest : streams={len(active)}/{len(sessions)} "
          f"ingested={ingested} delivered={delivered} shed={dropped} "
          f"(conserved={all(s.conserved() for s in sessions)})")
    for name, eng in engines.items():
        fills = eng.staging_fills
        bps = eng.staging_bytes / fills if fills else 0.0
        print(f"  {name}: staged {eng.staging_bytes}B over {fills} steps "
              f"({bps:.0f} B/step), host_allocs={eng.staging_host_allocs}, "
              f"decode_compiles={eng.stats['decode_compiles']}")


def serve_transport():
    """--transport: the full networked path. Every stream is a datagram
    client behind its own seed-derived chaotic link; the server
    reassembles, backpressures, and (if a slice dies) re-homes."""
    n_slices = max(2, args.slices)
    print(f"compiling + profiling {n_slices} slices (per-slice §4.1 pass)...")
    cluster, slices, _gateway, transport, _binding = build_live_transport(
        configs, categories,
        slice_names=tuple(f"slice{i}" for i in range(n_slices)),
        record_payloads=False,
        tracer=TRACER,
    )
    loop = cluster.loop
    clients, links = [], []
    for i, (cat, deadline, src) in enumerate(make_sources()):
        plan = LinkPlan.from_seed(
            args.chaos_seed + i, src.n_frames * 4,
            p_drop=0.05, p_dup=0.05, p_reorder=0.08, p_delay=0.05,
            reorder_hold=(0.05, 0.2),
        )
        link = SimLink(loop, transport.datagram, plan=plan)
        client = TransportSource(src, cat, deadline, link)
        ok = client.start(transport)
        ts = transport.sessions.get(client.sid)
        where = f" @{ts.session.slice_name}" if ok else ""
        print(f"stream {client.sid} ({cat}): "
              f"{'ADMIT' + where if ok else 'REJECT'}")
        clients.append(client)
        links.append(link)
    print("\nserving through the chaotic link (wall clock, zero-stall)...")
    cluster.run()
    transport.finalize_all()
    cluster.run(until=loop.now + 0.5)
    snap = json.loads(transport.status_json())
    print(f"link   : sends={sum(l.sends for l in links)} "
          f"dropped={sum(l.dropped for l in links)} "
          f"duplicated={sum(l.duplicated for l in links)} "
          f"reordered={sum(l.reordered for l in links)} "
          f"delayed={sum(l.delayed for l in links)}")
    for sid, sess in sorted(snap["sessions"].items(), key=lambda kv: int(kv[0])):
        w = sess["wire"]
        print(f"  session {sid} @{sess['slice']}: received={w['received']} "
              f"delivered={w['delivered']} dup={w['duplicates']} "
              f"lost={w['net_lost']} late={w['late_rejected']} "
              f"credit={sess['credit']:.2f} downshifts={sess['downshifts']} "
              f"conserved={w['conserved']}")
    agg = cluster.aggregate_metrics()
    print(f"cluster: completed={agg['completed_frames']} "
          f"missed={agg['missed_frames']} ({agg['miss_rate']:.1%}) "
          f"shed={agg['dropped_frames']} lost={agg['lost_frames']} "
          f"conserved={agg['completed_frames'] + agg['dropped_frames'] + agg['lost_frames'] == agg['ingested_frames']}")
    for name, sl in slices.items():
        print(f"  {name}: decode_compiles={sl.engine.stats['decode_compiles']} "
              f"device_busy={sl.device.busy_time:.2f}s")
    dump_trace()


if args.transport:
    if args.source is None:
        args.source = "camera"  # transport clients need payload sources
    serve_transport()
    sys.exit(0)

if args.slices > 1:
    print(f"compiling + profiling {args.slices} slices (per-slice §4.1 pass)...")
    cluster, slices = build_live_cluster(
        configs, categories,
        slice_names=tuple(f"slice{i}" for i in range(args.slices)),
        tracer=TRACER,
    )
    if args.source:
        serve_ingest(cluster, {n: sl.engine for n, sl in slices.items()})
        agg = cluster.aggregate_metrics()
        print(f"cluster: completed={agg['completed_frames']} "
              f"missed={agg['missed_frames']} ({agg['miss_rate']:.1%}) "
              f"shed={agg['dropped_frames']} "
              f"e2e={agg['mean_e2e_latency']*1e3:.1f}ms")
        dump_trace()
        sys.exit(0)
    for r in make_trace():
        r.start_time = 0.0
        ok = cluster.submit_request(r)
        where = cluster.placement.get(r.request_id, "-")
        print(f"request {r.request_id} ({r.category}): "
              f"{'ADMIT @' + where if ok else 'REJECT (all slices)'}")
    print("\nserving live across slices (one wall clock, zero-stall)...")
    cluster.run()
    agg = cluster.aggregate_metrics()
    print(f"cluster: completed={agg['completed_frames']} "
          f"missed={agg['missed_frames']} ({agg['miss_rate']:.1%}) "
          f"jobs={agg['jobs']} dropped={agg['dropped_requests']}")
    for name, sl in slices.items():
        m = sl.scheduler.metrics
        st = sl.engine.stats
        print(f"  {name}: frames={m.completed_frames} "
              f"decode_compiles={st['decode_compiles']} "
              f"prefill_compiles={st['prefill_compiles']} "
              f"device_busy={sl.device.busy_time:.2f}s")
    dump_trace()
    sys.exit(0)

print("compiling + profiling engine (paper §4.1 offline pass)...")
sched, engine, table = build_live_scheduler(configs, categories,
                                            tracer=TRACER)

if args.source:
    serve_ingest(sched, {"device0": engine})
    m = sched.metrics
    print(f"DeepRT : completed={m.completed_frames} missed={m.missed_frames} "
          f"({m.miss_rate:.1%}) shed={m.dropped_frames} "
          f"e2e={m.mean_e2e_latency*1e3:.1f}ms "
          f"sched-latency={m.mean_latency*1e3:.1f}ms")
    dump_trace()
    sys.exit(0)
for (mid, shape), batches in sorted(
    ((k, v) for k, v in table.entries.items()), key=lambda kv: kv[0]
):
    b1 = batches.get(1)
    b8 = batches.get(8)
    print(f"  {mid} shape={shape}: E(1)={b1*1e3:.1f}ms E(8)={b8*1e3:.1f}ms")

trace = make_trace()
accepted = []
for r in trace:
    r.start_time = 0.0
    res = sched.submit_request(r)
    print(
        f"request {r.request_id} ({r.category}): "
        f"{'ADMIT' if res.admitted else 'REJECT'} (U={res.utilization:.2f})"
    )
    if res.admitted:
        accepted.append(copy.deepcopy(r))

print("\nserving live (wall clock, async zero-stall dispatch)...")
m = sched.run()
print(
    f"DeepRT : completed={m.completed_frames} missed={m.missed_frames} "
    f"({m.miss_rate:.1%}) jobs={m.job_count} mean_batch={m.mean_batch:.2f}"
)
print(
    f"         host stall/job={m.mean_dispatch_overhead*1e6:.0f}us "
    f"padding_waste={m.padding_waste:.1%} "
    f"device_busy={sched.device.busy_time:.2f}s"
)

# Baseline on the same accepted trace, simulated with the measured table.
base = BATCH(table, loop=EventLoop(), batch_size=4)
for r in accepted:
    base.submit_request(copy.deepcopy(r))
bm = base.run()
print(
    f"BATCH-4: completed={bm.completed_frames} missed={bm.missed_frames} "
    f"({bm.miss_rate:.1%}) jobs={bm.job_count} mean_batch={bm.mean_batch:.2f}"
)
dump_trace()
