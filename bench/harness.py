"""One run of one cell: build the serving path, drive it, measure, check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names its configuration (``configs`` entry -> ``bench/configs/*.json``)
and its traffic mix (``bench/traffic/<traffic>.json``); each per-layer
metric is read by ``bench/metrics/<metric>.py``; the limits of the
correctness check are in ``bench/limits/<configuration>.json``. Adding a cell, a mix or a metric adds files and entries only.

The served path is the program's own: ``build_live_transport`` for one
slice on one chip (transport -> gateway -> admission -> DisBatcher/EDF
-> ``AsyncDevice`` -> ``InferenceEngine``), with its defaults. The
benchmark swaps in weights drawn from the seed (``model.make_weights``),
wraps the engine's dispatch to record what the window served
(``check.Recorder``), and sends open-loop traffic
(``traffic/generator.py``). A run is: build and profile, warm-up
traffic, the measured window of ``--seconds``, the drain of every frame
due in the window, then the reference check with the arena freed.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check as C
from bench import model as M
from bench import trace as TR
from bench.traffic import generator as G

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACER_CAPACITY = 4_000_000   # the whole run's span events, none evicted
TRAFFIC_LEAD = 0.25           # seconds from the first HELLO to traffic start
DRAIN_LIMIT = 60.0            # wait for window frames at most this long


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict             # bench/configs/<config>.json
    mix: Dict                # bench/traffic/<traffic>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    limits = json.loads((BENCH / "limits" / f"{w['config']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=G.load_mix(w["traffic"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limits=limits,
    )


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def program_config(config: Dict):
    """The program's ModelConfig with the configuration file's sizes."""
    from repro.configs.registry import get_config

    s = M.Shape.from_config(config)
    return get_config(
        config["program_arch"], n_layers=s.layers, d_model=s.d_model,
        n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab, rope_theta=s.rope_theta,
        tie_embeddings=True, param_dtype=s.dtype,
    )


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclass
class Window:
    """Readings of the measured window, handed to the metric readers."""

    seconds: float
    shape: M.Shape
    peak: Optional[Dict]
    frames: List[Dict]                 # one per frame due in the window
    offered: int                       # streams offered
    admitted: int                      # streams admitted
    agg: Dict[str, Dict]               # aggregate_metrics at open / close
    stats: Dict[str, Dict]             # engine.stats at open / close
    recorder: C.Recorder
    tracer_events: Optional[list] = None
    trace: Optional[Dict] = None       # trace.reduce of the window
    platform: str = "tpu"
    extra: Dict = field(default_factory=dict)

    def delta(self, table: str, key: str) -> float:
        t = getattr(self, table)
        return t["close"][key] - t["open"][key]


def _snapshot(cluster, engine) -> Dict:
    return {"agg": cluster.aggregate_metrics(), "stats": dict(engine.stats),
            "adaptation": [sl.scheduler.adaptation.telemetry()
                           for sl in cluster.slices.values()]}


def _install_weights(engine, mid: str, shape: M.Shape, seed: int):
    """Replace the program's own random weights with the seed's."""
    import jax

    want = M.abstract_weights(shape)
    have = engine.params[mid]
    same = jax.tree.structure(want) == jax.tree.structure(have) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)))
    if not same:
        raise RuntimeError("the program's weight layout differs from "
                           "bench/model.py weight_layout")
    del have
    engine.params[mid] = None  # free the program's weights first
    engine.params[mid] = M.make_weights(shape, seed, engine.device)
    jax.block_until_ready(engine.params[mid])
    return engine.params[mid]


def _warm_buckets(engine, mid: str, admitted: Dict[int, int]) -> None:
    """Compile and run, before the traffic, the prefill batch buckets
    above the profiled ones (1-8) that the admitted streams can fill:
    a window as long as the period holds up to two jittered frames of a
    stream, and the engine compiles a new bucket on its first use."""
    from repro.core.bucketing import bucket

    for tokens, n in admitted.items():
        b = 16
        while b <= bucket(2 * n):
            engine.execute(mid, (tokens,), b, "prefill")
            b *= 2


def categories(mid: str, mix: Dict):
    cats = []
    for g in mix["streams"]:
        if g["kind"] == "prefill":
            cat = (mid, (int(g["tokens"]),), "prefill")
        else:
            cat = (mid, (int(mix["arena_tokens"]),), "decode")
        if cat not in cats:
            cats.append(cat)
    return cats


@dataclass
class Stack:
    """The built serving path of one cell, compiled and profiled."""

    cluster: object
    slices: Dict
    gateway: object
    transport: object
    engine: object
    mid: str
    shape: M.Shape
    peak: Dict
    device: object
    tracer: Optional[object] = None

    def close(self) -> None:
        for sl in self.slices.values():
            sl.device.close()


def build(cell: Cell, trace: bool, program_cfg=None,
          peaks: Optional[Dict] = None) -> Stack:
    """``build_live_transport`` for one slice on one chip, with the
    program's defaults. ``program_cfg`` and ``peaks`` exist for the CPU
    rehearsal: a reduced configuration, and peaks for the CPU."""
    import jax

    from repro.core.telemetry import FrameTracer
    from repro.serving.batcher_bridge import build_live_transport

    dev = jax.devices()[0]
    if peaks is None:
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} "
                         f"in bench/peaks.json")
    shape = M.Shape.from_config(cell.config)
    cfg = program_cfg if program_cfg is not None else program_config(cell.config)
    if program_cfg is not None:
        shape = dataclasses.replace(
            shape, layers=cfg.n_layers, d_model=cfg.d_model, heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
            d_ff=cfg.d_ff, vocab=cfg.vocab_size, dtype=cfg.param_dtype)
    mid = cfg.arch_id
    tracer = FrameTracer(capacity=TRACER_CAPACITY) if trace else None
    cluster, slices, gateway, transport, _ = build_live_transport(
        {mid: cfg}, categories(mid, cell.mix), slice_names=("slice0",),
        tracer=tracer,
    )
    return Stack(cluster, slices, gateway, transport, slices["slice0"].engine,
                 mid, shape, peaks[dev.device_kind], dev, tracer)


def serve(stack: Stack, mix: Dict, seed: int, seconds: float, trace: bool,
          fault: Optional[Callable] = None) -> Window:
    """Weights from ``seed``, warm-up, the window, the drain. ``fault``
    (CPU tests only) gets the stack before traffic starts, to break the
    timed path underneath."""
    import jax

    from repro.core import Category
    from repro.ingest import SimLink

    cluster, transport, engine = stack.cluster, stack.transport, stack.engine
    sl = stack.slices["slice0"]
    loop = cluster.loop
    weights = _install_weights(engine, stack.mid, stack.shape, seed)
    if fault is not None:
        fault(stack)
    decode_periods = [g["period_s"] for g in mix["streams"] if g["kind"] == "decode"]
    rec = C.Recorder(
        engine, loop, seed,
        expected_steps=seconds / min(decode_periods) if decode_periods else 1.0,
        annotate=trace,
    )
    warmup = float(mix["warmup_s"])
    # Streams go on past the window for twice the longest deadline, so
    # the load holds steady to its close and no stream ends inside it.
    tail = 2 * max(g["deadline_s"] for g in mix["streams"]) + 0.5
    streams = G.plan(mix, seed, warmup + seconds + tail)
    clients, admitted = [], {}
    for st in streams:
        src = G.PlannedSource(st, stack.shape.vocab)
        client = G.OpenLoopClient(
            src, Category(stack.mid, (st.tokens,)),
            SimLink(loop, transport.datagram))
        clients.append(client)
        if client.start(transport) and st.kind == "prefill":
            admitted[st.tokens] = admitted.get(st.tokens, 0) + 1
    _warm_buckets(engine, stack.mid, admitted)
    t_traffic = loop.now + TRAFFIC_LEAD
    for st, client in zip(streams, clients):
        client.arm(t_traffic + st.start)
    t_open = t_traffic + warmup
    t_close = t_open + seconds
    marks: Dict[str, object] = {}

    compiles = {"n": 0, "open": False}

    def on_compile(event, secs, **_kw):
        if compiles["open"] and event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    def open_window():
        compiles["open"] = True
        marks["open"] = _snapshot(cluster, engine)
        marks["open_wall"] = time.perf_counter()
        rec.window = (t_open, t_close)
        if trace:
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()

    def close_window():
        compiles["open"] = False
        marks["close"] = _snapshot(cluster, engine)
        if trace:
            marks["span"].__exit__(None, None, None)

    loop.schedule(t_open, open_window, priority=0)
    loop.schedule(t_close, close_window, priority=0)
    _serve(cluster, transport, clients, streams, sl, t_open, t_close)
    rec.close()
    frames = _window_frames(clients, streams, transport, sl, t_open, t_close)
    win = Window(
        seconds=seconds, shape=stack.shape, peak=stack.peak, frames=frames,
        offered=len(clients),
        admitted=sum(c.state != "rejected" for c in clients),
        agg={k: marks[k]["agg"] for k in ("open", "close")},
        stats={k: marks[k]["stats"] for k in ("open", "close")},
        recorder=rec, platform=stack.device.platform,
    )
    win.extra.update(
        open_wall=marks["open_wall"], weights=weights,
        totals=cluster.aggregate_metrics(),
        sessions=list(transport.sessions.values()),
        lateness=[x for c in clients for i, x in enumerate(c.lateness)
                  if t_open <= c.t0 + c.plan[i].offset < t_close],
    )
    if stack.tracer is not None:
        win.tracer_events = list(stack.tracer.ring)
        log(f"tracer: {stack.tracer.emitted} events, "
            f"{stack.tracer.evicted} evicted")
    log(f"compiles inside the window: {compiles['n']} (engine: prefill "
        f"{win.delta('stats', 'prefill_compiles')}, decode "
        f"{win.delta('stats', 'decode_compiles')})")
    buckets: Dict[int, int] = {}
    for b in rec.prefill:
        buckets[b.bucket] = buckets.get(b.bucket, 0) + 1
    log(f"window dispatches: prefill buckets {dict(sorted(buckets.items()))}, "
        f"decode steps {len(rec.decode)}")
    for k in ("open", "close"):
        a = marks[k]["adaptation"][0]
        log(f"adaptation at window {k}: penalties {a['penalties']}, "
            f"shape changes {a['shape_changes']}, restores {a['restores']}")
    _log_lateness(win.extra["lateness"])
    _log_sheds(stack.gateway, win)
    return win


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
        program_cfg=None, peaks: Optional[Dict] = None,
        fault: Optional[Callable] = None) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    stack = build(cell, trace, program_cfg, peaks)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    try:
        win = serve(stack, cell.mix, seed, seconds, trace, fault)
    finally:
        stack.close()
        if trace:
            jax.profiler.stop_trace()
    setup_s = win.extra["open_wall"] - t_process
    mem = stack.device.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    if trace:
        t0 = time.perf_counter()
        events = TR.load(log_dir, win.platform)
        win.trace = TR.reduce(events, TR.window_of(events))
        shutil.rmtree(log_dir, ignore_errors=True)
        log(f"trace: {len(events['ops'])} device ops, reduced in "
            f"{time.perf_counter() - t0:.1f} s")

    # The program's arena goes before the reference runs.
    stack.engine._arenas.clear()
    t0 = time.perf_counter()
    gaps = C.evaluate(win.recorder, stack.shape, win.extra["weights"], seed)
    log(f"reference check: {time.perf_counter() - t0:.1f} s, "
        f"{gaps['prefill_compared']} prefill and {gaps['decode_compared']} "
        f"decode tokens compared")
    checks, reasons = _checks(cell, cell.mix, win, gaps)
    frames = win.frames
    result = {
        "correct": False,
        "attempted": len(frames),
        "failed": sum(f["status"] in ("lost", "unanswered") for f in frames),
        "metrics": {},
        "device": {"platform": win.platform, "kind": stack.device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    if trace:
        red = win.trace
        result["device"]["busy_s"] = red["busy_ns"] / 1e9
        result["device"]["window_s"] = red["window_ns"] / 1e9
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in red["device_ops"]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in red["idle_gaps"]],
        }
        log("idle by host span (s): " + json.dumps(
            {k: v / 1e9 for k, v in red["idle_by_host"].items()}))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(win)
            # Only a share of a roofline or of a peak reads None: with
            # nothing to read it is left out, never printed as 0.
            if value is None:
                reasons.append(f"{m['name']}: nothing to read in the window")
                continue
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(win, setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    if not any(f["completion"] is not None for f in frames):
        reasons.append("no frame due in the window completed")
    result["correct"] = not reasons
    for r in reasons:
        log(f"not correct: {r}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["check"] = checks
    return result


def _serve(cluster, transport, clients, streams, sl, t_open, t_close) -> None:
    """Run the loop through the window, then until every frame due in it
    is resolved (answered, shed, or lost) and nothing is left in flight,
    at most DRAIN_LIMIT past the close; then close every session."""
    loop = cluster.loop
    longest = max(st.deadline for st in streams)
    cluster.run(until=t_close + longest + 0.5)
    while loop.now < t_close + DRAIN_LIMIT:
        agg = cluster.aggregate_metrics()
        busy = agg["ingested_frames"] - (agg["completed_frames"]
                                         + agg["dropped_frames"] + agg["lost_frames"])
        if busy <= 0 and not _unresolved(clients, transport, sl, t_open, t_close):
            break
        cluster.run(until=loop.now + 0.25)
    transport.finalize_all()
    cluster.run(until=loop.now + 0.1)


def _unresolved(clients, transport, sl, t_open, t_close) -> int:
    """Frames due in the window not yet answered, shed or lost."""
    records = sl.scheduler.metrics.frame_records
    n = 0
    for c in clients:
        if c.state == "rejected" or c.sid is None:
            continue
        ts = transport.sessions.get(c.sid)
        if ts is None:
            continue
        rid = ts.session.request_id
        delivered = set(ts.delivered_log)
        for i, p in enumerate(c.plan):
            due = c.t0 + p.offset
            if t_open <= due < t_close and (rid, i) not in records \
                    and (i not in ts.seen or i in delivered):
                n += 1
    return n


def _window_frames(clients, streams, transport, sl, t_open, t_close) -> List[Dict]:
    """Every frame due in the window, from every offered stream."""
    records = sl.scheduler.metrics.frame_records
    out = []
    for c, st in zip(clients, streams):
        ts = transport.sessions.get(c.sid) if c.sid is not None else None
        delivered = set(ts.delivered_log) if ts is not None else set()
        rid = ts.session.request_id if ts is not None else None
        for i, p in enumerate(c.plan):
            due = c.t0 + p.offset
            if not t_open <= due < t_close:
                continue
            rec = records.get((rid, i)) if rid is not None else None
            if c.state == "rejected":
                status = "rejected"
            elif rec is not None:
                status = "completed"
            elif ts is not None and i in ts.seen and i not in delivered:
                status = "shed"
            elif i in delivered:
                status = "unanswered"
            else:
                status = "lost"
            out.append({
                "kind": st.kind, "rid": rid, "idx": i, "due": due,
                "deadline": due + st.deadline, "status": status,
                "completion": rec[2] if rec is not None else None,
            })
    return out


def end_to_end(win: Window, setup_s: float) -> Dict[str, float]:
    lat = sorted(f["completion"] - f["due"] for f in win.frames
                 if f["completion"] is not None)
    ontime = sum(1 for f in win.frames if f["completion"] is not None
                 and f["completion"] <= f["deadline"])
    pct = (lambda q: float(np.percentile(lat, q)) * 1e3) if lat else (lambda q: 0.0)
    return {
        "ontime_fps": ontime / win.seconds,
        "e2e_p50_ms": pct(50),
        "e2e_p95_ms": pct(95),
        "setup_s": setup_s,
    }


def _checks(cell, mix, win, gaps):
    """Each compared number beside its limit, and the reasons a run is
    not correct."""
    reasons: List[str] = []
    lim = cell.limits
    totals, sessions = win.extra["totals"], win.extra["sessions"]
    breach = abs(totals["completed_frames"] + totals["dropped_frames"]
                 + totals["lost_frames"] - totals["ingested_frames"])
    breach += sum(not ts.wire_conserved() for ts in sessions)
    unanswered = sum(f["status"] in ("lost", "unanswered") for f in win.frames)
    checks = {
        "conservation": {"value": breach, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "arena_overflow": {"value": win.recorder.overflow, "limit": 0},
    }
    kinds = {g["kind"] for g in mix["streams"]}
    for kind in sorted(kinds):
        value = gaps[f"{kind}_gap"]
        checks[f"{kind}_gap"] = {"value": value, "limit": lim[f"{kind}_gap"]}
        if value is None:
            reasons.append(f"no {kind} output was served in the window")
    for name, c in checks.items():
        if c["value"] is not None and c["value"] > c["limit"]:
            reasons.append(f"{name} {c['value']} over its limit {c['limit']}")
    return checks, reasons


def _log_lateness(lateness: List[float]) -> None:
    if not lateness:
        log("generator: no send due in the window")
        return
    ms = np.array(lateness) * 1e3
    log(f"generator lateness over {len(ms)} sends: p50 {np.percentile(ms, 50):.3f} ms, "
        f"p99 {np.percentile(ms, 99):.3f} ms, max {ms.max():.3f} ms")


def _log_sheds(gateway, win: Window) -> None:
    """Where shed frames came from: the gateway's reasons and the terms
    of its delay estimate, and, in a traced run, sheds per second."""
    shed = [f for f in win.frames if f["status"] == "shed"]
    log(f"window frames: {len(win.frames)} due, "
        + ", ".join(f"{s} {sum(f['status'] == s for f in win.frames)}"
                    for s in ("completed", "shed", "rejected", "unanswered", "lost")))
    if not shed:
        return
    reasons: Dict[str, int] = {}
    for s in gateway.sessions:
        if s.last_shed_reason:
            key = s.last_shed_reason.split(":")[0]
            reasons[key] = reasons.get(key, 0) + 1
    log(f"sessions' last shed reasons: {reasons}; e.g. "
        f"{next(s.last_shed_reason for s in gateway.sessions if s.last_shed_reason)}")
    if win.tracer_events is not None:
        from repro.core import telemetry as T

        t0 = win.recorder.window[0]
        per_s: Dict[int, int] = {}
        terms: Dict[str, List[float]] = {}
        for ev in win.tracer_events:
            if ev.stage == T.SHED and t0 <= ev.t < win.recorder.window[1]:
                per_s[int(ev.t - t0)] = per_s.get(int(ev.t - t0), 0) + 1
                for k, v in ((ev.meta or {}).get("breakdown") or {}).items():
                    terms.setdefault(k, []).append(v)
        log(f"sheds per second of the window: {[per_s.get(i, 0) for i in range(int(win.seconds))]}")
        log("shed delay-estimate terms (mean ms): " + ", ".join(
            f"{k} {statistics.fmean(v) * 1e3:.2f}" for k, v in terms.items()))
