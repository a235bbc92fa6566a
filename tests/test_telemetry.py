"""Frame-lifecycle telemetry tests (core/telemetry.py + its wiring).

Covers the observability PR's acceptance bars:

- span ordering / terminal completeness: every delivered frame's trace
  is time-ordered and ends in EXACTLY ONE terminal span (completed /
  late / shed / lost) — the trace-level mirror of the conservation
  identity ``completed + dropped + lost == ingested``;
- ring-capacity eviction correctness (bounded memory, counted losses);
- deadline-miss attribution on a deterministic 2x overload: every
  missed frame carries a per-stage budget that sums to its observed
  latency (float tolerance), aggregated per category in the snapshot;
- streaming log-bucket histogram accuracy vs exact samples (the slow
  lane runs a hypothesis sweep);
- Metrics stays O(1)-memory with ``record_samples=False``;
- sim-vs-live trace-shape determinism: the same admitted stream under
  the EventLoop and under a WallClock + AsyncDevice produces the same
  per-frame stage sequences.
"""
from __future__ import annotations

import json
import math
import os

import pytest

from repro.core import telemetry as T
from repro.core import (
    Category,
    DeepRT,
    FrameTracer,
    LatencyHistogram,
    Metrics,
    ProfileTable,
    Request,
    WallClock,
    build_sim_cluster,
    render_text,
)
from repro.ingest import BurstSource, IngestGateway
from repro.serving.async_device import AsyncDevice

MID = "m"
SHAPE = (4,)
CAT = Category(MID, SHAPE)


def _table() -> ProfileTable:
    table = ProfileTable()
    for b in (1, 2, 4, 8, 16, 32):
        table.record(MID, SHAPE, b, 0.01 + 0.04 * b)
    return table


def _frame_traces(tracer: FrameTracer):
    """Group ring events per (rid, idx) frame, preserving emit order."""
    frames = {}
    for ev in tracer.ring:
        if ev.rid >= 0 and ev.idx >= 0:
            frames.setdefault((ev.rid, ev.idx), []).append(ev)
    return frames


# ---------------------------------------------------------------------------
# Span ordering + terminal completeness
# ---------------------------------------------------------------------------
class TestSpanLifecycle:
    def _run(self, relative_deadline: float, n_frames: int = 12):
        sched = DeepRT(_table())
        tracer = FrameTracer()
        sched.attach_tracer(tracer, tag="solo")
        req = Request(category=CAT, period=0.1, n_frames=n_frames,
                      relative_deadline=relative_deadline)
        assert sched.submit_request(req).admitted
        metrics = sched.run()
        return sched, tracer, metrics

    def test_every_frame_ends_in_exactly_one_terminal(self):
        _sched, tracer, metrics = self._run(relative_deadline=0.5)
        frames = _frame_traces(tracer)
        assert len(frames) == 12
        for key, events in frames.items():
            times = [ev.t for ev in events]
            assert times == sorted(times), (key, events)
            terminals = [ev for ev in events if ev.stage in T.TERMINAL_STAGES]
            assert len(terminals) == 1, (key, [ev.stage for ev in events])
            # The terminal is the LAST span of the frame's lifecycle.
            assert events[-1] is terminals[0], (key, events)
            assert events[0].stage == T.INGEST, (key, events)
        # Trace-level conservation mirrors the metrics identity.
        assert tracer.terminals.get(T.COMPLETED, 0) == metrics.completed_frames
        assert sum(tracer.terminals.values()) == metrics.delivered_frames
        # All frames closed out: no leaked open-stamp state.
        assert not tracer._open

    def test_full_stage_sequence_on_healthy_run(self):
        _sched, tracer, _metrics = self._run(relative_deadline=0.5)
        for key, events in _frame_traces(tracer).items():
            stages = [ev.stage for ev in events]
            assert stages == [T.INGEST, T.WINDOW_CLOSE, T.EDF_ENQUEUE,
                              T.EDF_DISPATCH, T.COMPLETED], (key, stages)

    def test_overloaded_frames_still_one_terminal_each(self):
        # Admission sees the profiled WCET; reality runs 4x over it, so
        # frames go late — lateness must not double-count or skip
        # terminals.
        from repro.core import ExecutionModel

        sched = DeepRT(_table(), execution=ExecutionModel(
            actual_fn=lambda job, w: 4.0 * w))
        tracer = FrameTracer()
        sched.attach_tracer(tracer, tag="solo")
        req = Request(category=CAT, period=0.1, n_frames=12,
                      relative_deadline=0.15)
        assert sched.submit_request(req).admitted
        metrics = sched.run()
        assert metrics.missed_frames > 0
        for key, events in _frame_traces(tracer).items():
            terminals = [ev for ev in events if ev.stage in T.TERMINAL_STAGES]
            assert len(terminals) == 1, (key, [ev.stage for ev in events])
        assert tracer.terminals.get(T.LATE, 0) == metrics.missed_frames
        assert not tracer._open

    def test_events_tagged_with_slice_and_category(self):
        _sched, tracer, _metrics = self._run(relative_deadline=0.5)
        for ev in tracer.ring:
            assert ev.where == "solo"
            if ev.rid >= 0:
                assert ev.cat == str(CAT)


# ---------------------------------------------------------------------------
# Ring eviction
# ---------------------------------------------------------------------------
class TestRingEviction:
    def test_ring_keeps_newest_and_counts_evictions(self):
        tracer = FrameTracer(capacity=16)
        for i in range(50):
            tracer.emit(T.ADMISSION, float(i), where="s0", cat="c")
        assert len(tracer.ring) == 16
        assert tracer.emitted == 50
        assert tracer.evicted == 34
        assert [ev.t for ev in tracer.ring] == [float(i) for i in range(34, 50)]

    def test_eviction_does_not_corrupt_attribution(self):
        # Stamps live outside the ring: a frame whose early spans were
        # evicted still gets a full, correctly-summing breakdown.
        tracer = FrameTracer(capacity=4)
        tracer.emit(T.INGEST, 1.0, 7, 0, where="s0", cat="c")
        for i in range(10):  # flush the ring well past capacity
            tracer.emit(T.ADMISSION, 2.0 + i, where="s0", cat="c")
        tracer.emit(T.EDF_DISPATCH, 20.0, 7, 0, where="s0", cat="c",
                    meta={"profiled": 0.5})
        tracer.emit(T.LATE, 21.0, 7, 0, where="s0", cat="c")
        assert len(tracer.miss_log) == 1
        entry = tracer.miss_log[0]
        assert entry["total"] == pytest.approx(20.0)
        assert sum(entry["stages"].values()) == pytest.approx(entry["total"])

    def test_miss_log_capped_with_overflow_counter(self):
        tracer = FrameTracer(miss_log_cap=8)
        for i in range(20):
            tracer.emit(T.INGEST, float(i), 1, i, where="s0", cat="c")
            tracer.emit(T.LATE, float(i) + 0.5, 1, i, where="s0", cat="c")
        assert len(tracer.miss_log) == 8
        assert tracer.miss_log_overflow == 12
        # Aggregates keep counting past the log cap.
        agg = tracer.attribution()["by_category"]["c"]
        assert agg["frames"] == 20


# ---------------------------------------------------------------------------
# Deadline-miss attribution (THE acceptance bar)
# ---------------------------------------------------------------------------
class TestMissAttribution:
    def _overload(self, shedding: bool, n_frames: int = 40):
        """Deterministic 2x overload replay: the declared-rate stream
        delivers its whole frame budget in half the admitted time."""
        sched = DeepRT(_table())
        tracer = FrameTracer()
        sched.attach_tracer(tracer, tag="s0")
        gw = IngestGateway(sched, shedding=shedding)
        gw.tracer = tracer
        src = BurstSource(period=0.1, n_frames=n_frames, burst=4, duty=0.5,
                          payload_shape=SHAPE, seed=11)
        session = gw.register(src, CAT, relative_deadline=0.2)
        assert session.state == "active"
        metrics = sched.run()
        return session, tracer, metrics

    def test_every_miss_sums_to_observed_latency(self):
        _session, tracer, metrics = self._overload(shedding=False)
        assert metrics.missed_frames > 0
        assert len(tracer.miss_log) == metrics.missed_frames
        for entry in tracer.miss_log:
            assert set(entry["stages"]) == set(T.ATTR_STAGES), entry
            total = sum(entry["stages"].values())
            assert abs(total - entry["total"]) < 1e-9, entry
            assert entry["total"] > 0.0, entry
            assert all(v >= 0.0 for v in entry["stages"].values()), entry

    def test_aggregation_per_category_matches_entries(self):
        _session, tracer, metrics = self._overload(shedding=False)
        attr = tracer.attribution()
        agg = attr["by_category"][str(CAT)]
        assert agg["frames"] == metrics.missed_frames
        assert agg["total"] == pytest.approx(
            sum(e["total"] for e in tracer.miss_log))
        for stage in T.ATTR_STAGES:
            assert agg[stage] == pytest.approx(
                sum(e["stages"][stage] for e in tracer.miss_log))
        # Slice-scoped aggregation sees the same mass.
        assert attr["by_slice"]["s0"]["total"] == pytest.approx(agg["total"])

    def test_shed_frames_get_terminal_and_attribution_bucket(self):
        session, tracer, metrics = self._overload(shedding=True)
        assert metrics.dropped_frames > 0
        assert tracer.terminals.get(T.SHED, 0) == metrics.dropped_frames
        # Conservation at the trace level, shed included.
        assert sum(tracer.terminals.values()) == session.frames_ingested
        attr = tracer.attribution()
        assert "shed" in attr and "lost" in attr
        shed_events = [ev for ev in tracer.ring if ev.stage == T.SHED]
        assert shed_events and all(
            ev.meta and ev.meta.get("reason") for ev in shed_events)

    def test_lost_frames_terminalized_on_dead_device(self):
        sched = DeepRT(_table())
        tracer = FrameTracer()
        sched.attach_tracer(tracer, tag="s0")
        req = Request(category=CAT, period=0.1, n_frames=3,
                      relative_deadline=0.5)
        assert sched.submit_request(req, external_arrivals=True).admitted
        sched.device._closed = True
        for i in range(3):
            sched.ingest_frame(req, i)
        assert tracer.terminals.get(T.LOST, 0) == 3
        assert sched.metrics.lost_frames == 3


# ---------------------------------------------------------------------------
# Streaming histogram
# ---------------------------------------------------------------------------
class TestLatencyHistogram:
    def test_exact_count_sum_min_max(self):
        hist = LatencyHistogram()
        samples = [0.001 * (i + 1) for i in range(100)]
        for v in samples:
            hist.record(v)
        assert hist.n == 100
        assert hist.total == pytest.approx(sum(samples))
        assert hist.vmin == pytest.approx(min(samples))
        assert hist.vmax == pytest.approx(max(samples))
        assert hist.mean == pytest.approx(sum(samples) / 100)

    def test_percentile_within_one_growth_factor(self):
        hist = LatencyHistogram(growth=1.08)
        samples = [0.0005 * (i + 1) ** 1.3 for i in range(500)]
        for v in samples:
            hist.record(v)
        ordered = sorted(samples)
        for q in (0.5, 0.9, 0.95, 0.99, 1.0):
            exact = ordered[max(1, math.ceil(q * len(ordered))) - 1]
            est = hist.percentile(q)
            assert exact * (1 - 1e-9) <= est <= exact * 1.08 * (1 + 1e-9), (
                q, exact, est)

    def test_under_and_overflow_clamped_to_observed(self):
        hist = LatencyHistogram(min_value=1e-3, max_value=1.0)
        hist.record(1e-6)   # underflow bucket
        hist.record(50.0)   # overflow bucket
        assert hist.n == 2
        assert hist.percentile(1.0) == pytest.approx(50.0)  # clamp to vmax
        assert hist.percentile(0.0) <= 1e-3

    def test_merge_equals_union(self):
        a, b, u = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
        xs = [0.002 * (i + 1) for i in range(40)]
        ys = [0.05 * (i + 1) for i in range(40)]
        for v in xs:
            a.record(v)
            u.record(v)
        for v in ys:
            b.record(v)
            u.record(v)
        a.merge(b)
        assert a.n == u.n
        assert a.total == pytest.approx(u.total)
        assert a.counts == u.counts
        assert a.percentile(0.95) == pytest.approx(u.percentile(0.95))

    def test_merge_rejects_mismatched_layout(self):
        a = LatencyHistogram(growth=1.08)
        b = LatencyHistogram(growth=1.5)
        with pytest.raises(ValueError):
            a.merge(b)

    @pytest.mark.slow
    def test_percentile_accuracy_random_samples(self):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis (installed in CI)",
        )
        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        @settings(
            max_examples=int(os.environ.get("REPRO_HYPOTHESIS_EXAMPLES", "25")),
            deadline=None,
            suppress_health_check=[HealthCheck.too_slow],
        )
        @given(
            samples=st.lists(
                st.floats(min_value=1e-5, max_value=1e4,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=400),
            q=st.floats(min_value=0.0, max_value=1.0),
        )
        def check(samples, q):
            hist = LatencyHistogram()
            for v in samples:
                hist.record(v)
            ordered = sorted(samples)
            exact = ordered[max(1, math.ceil(q * len(ordered))) - 1]
            est = hist.percentile(q)
            # Conservative (never under-reports beyond fp noise) and
            # within one growth factor of the exact sample quantile.
            assert est >= exact * (1 - 1e-9)
            assert est <= exact * hist.growth * (1 + 1e-9)

        check()


# ---------------------------------------------------------------------------
# Metrics memory behavior
# ---------------------------------------------------------------------------
class TestMetricsMemory:
    def _run(self, record_samples: bool):
        sched = DeepRT(_table())
        sched.metrics.record_samples = record_samples
        req = Request(category=CAT, period=0.1, n_frames=20,
                      relative_deadline=0.5)
        assert sched.submit_request(req).admitted
        return sched.run()

    def test_record_samples_false_keeps_lists_empty(self):
        m = self._run(record_samples=False)
        assert m.completed_frames == 20
        assert m.frame_latencies == [] and m.e2e_latencies == []
        # Aggregates stay exact without the sample lists.
        assert m.latency_hist.n == 20 and m.e2e_hist.n == 20
        assert m.mean_latency > 0.0 and m.mean_e2e_latency > 0.0
        assert m.latency_percentile(0.99) >= m.latency_percentile(0.5) > 0.0

    def test_default_keeps_samples_and_agrees_with_hist(self):
        m = self._run(record_samples=True)
        assert len(m.frame_latencies) == 20
        assert m.mean_latency == pytest.approx(
            sum(m.frame_latencies) / 20, rel=1e-9)

    def test_metrics_standalone_flag(self):
        m = Metrics(record_samples=False)
        assert m.record_samples is False


# ---------------------------------------------------------------------------
# Sim-vs-live trace-shape determinism
# ---------------------------------------------------------------------------
class _InstantHandle:
    def wait(self):
        return None


class TestSimLiveTraceShape:
    def _shapes(self, tracer: FrameTracer):
        return {key: [ev.stage for ev in events]
                for key, events in _frame_traces(tracer).items()}

    def test_same_stream_same_stage_sequences(self):
        n_frames = 4
        # Sim arm: EventLoop + SequentialDevice.
        sim = DeepRT(_table())
        sim_tr = FrameTracer()
        sim.attach_tracer(sim_tr, tag="s0")
        req = Request(category=CAT, period=0.08, n_frames=n_frames,
                      relative_deadline=0.3)
        assert sim.submit_request(req).admitted
        sim.run()

        # Live arm: WallClock + AsyncDevice over an instant backend.
        loop = WallClock()
        live = DeepRT(_table(), loop=loop,
                      device=AsyncDevice(loop, lambda job: _InstantHandle()))
        live_tr = FrameTracer()
        live.attach_tracer(live_tr, tag="s0")
        req2 = Request(category=CAT, period=0.08, n_frames=n_frames,
                       relative_deadline=0.3)
        assert live.submit_request(req2).admitted
        live.loop.run(until=live.loop.now + 2.0)

        sim_shapes = self._shapes(sim_tr)
        live_shapes = self._shapes(live_tr)
        # Rekey by frame index: request ids differ across schedulers.
        sim_by_idx = {idx: v for (_rid, idx), v in sim_shapes.items()}
        live_by_idx = {idx: v for (_rid, idx), v in live_shapes.items()}
        assert sim_by_idx == live_by_idx
        assert len(sim_by_idx) == n_frames
        assert sim_tr.terminals == live_tr.terminals


# ---------------------------------------------------------------------------
# Cluster snapshot + exposition + chrome export
# ---------------------------------------------------------------------------
class TestClusterTelemetry:
    def _cluster(self):
        cluster = build_sim_cluster(_table, ("s0", "s1"))
        tracer = FrameTracer()
        cluster.attach_tracer(tracer)
        req = Request(category=CAT, period=0.1, n_frames=10,
                      relative_deadline=0.5)
        assert cluster.submit_request(req)
        cluster.run()
        return cluster, tracer

    def test_snapshot_is_json_serializable_and_complete(self):
        cluster, _tracer = self._cluster()
        snap = cluster.telemetry_snapshot()
        json.dumps(snap)  # must round-trip
        assert set(snap["slices"]) == {"s0", "s1"}
        for name, sl in snap["slices"].items():
            assert sl["health"] and "utilization" in sl, name
            assert "latency" in sl and "e2e" in sl, name
        assert snap["aggregate"]["completed_frames"] == 10
        assert "e2e_p99" in snap["aggregate"]
        assert snap["tracer"]["emitted"] > 0
        assert snap["attribution"]["terminals"].get("completed", 0) == 10

    def test_text_exposition_renders_numeric_leaves(self):
        cluster, _tracer = self._cluster()
        text = cluster.telemetry_text()
        lines = text.strip().splitlines()
        assert lines == sorted(lines)
        assert any(l.startswith("deeprt_aggregate_completed_frames ")
                   for l in lines), text
        for line in lines:
            name, value = line.rsplit(" ", 1)
            float(value)  # every exposed leaf is numeric

    def test_chrome_trace_export(self, tmp_path):
        _cluster, tracer = self._cluster()
        doc = tracer.chrome_trace()
        assert doc["traceEvents"]
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("i", "X")
            assert ev["ts"] >= 0.0
        out = tmp_path / "trace.json"
        tracer.dump_chrome_trace(str(out))
        loaded = json.loads(out.read_text())
        assert len(loaded["traceEvents"]) == len(doc["traceEvents"])

    def test_tracer_default_off(self):
        sched = DeepRT(_table())
        assert sched.tracer is None
        assert sched.worker.tracer is None
        assert sched.disbatcher.tracer is None
        cluster = build_sim_cluster(_table, ("s0",))
        assert cluster.tracer is None


# ---------------------------------------------------------------------------
# Capped unbounded-growth logs (satellite)
# ---------------------------------------------------------------------------
class TestCappedLogs:
    def test_chunk_log_is_capped_deque(self):
        from collections import deque

        from repro.core.edf import CHUNK_LOG_CAP

        sched = DeepRT(_table())
        assert isinstance(sched.worker.chunk_log, deque)
        assert sched.worker.chunk_log.maxlen == CHUNK_LOG_CAP
        assert sched.worker.chunk_log_overflow == 0

    def test_placement_attempts_capped_with_overflow(self):
        from collections import deque

        cluster = build_sim_cluster(_table, ("s0",))
        assert cluster.placement_attempts.maxlen is not None
        # Shrink the audit trail so the eviction path is cheap to hit;
        # the overflow logic keys off the deque's own maxlen.
        cluster.placement_attempts = deque(maxlen=8)
        for i in range(13):
            req = Request(category=CAT, period=10.0, n_frames=1,
                          relative_deadline=0.5)
            cluster.submit_request(req)
        assert len(cluster.placement_attempts) == 8
        assert cluster.placement_attempts_overflow == 5


# ---------------------------------------------------------------------------
# Device-idle split, loop lag, engine counters, profiler spans
# ---------------------------------------------------------------------------
def _idle(sched, now):
    t = sched.worker.idle_clock.totals(now)
    return t[T.IDLE_HELD], t[T.IDLE_READY], t[T.IDLE_EMPTY], t[T.DEVICE_RUNNING]


def _live(tracer=None):
    """A WallClock DeepRT over an AsyncDevice whose jobs finish at once."""
    loop = WallClock()
    sched = DeepRT(_table(), loop=loop,
                   device=AsyncDevice(loop, lambda job: _InstantHandle()))
    if tracer is not None:
        sched.attach_tracer(tracer, tag="s0")
    return sched


def _serve_live(sched, n_frames=6, seconds=0.8):
    req = Request(category=CAT, period=0.08, n_frames=n_frames,
                  relative_deadline=0.3)
    assert sched.submit_request(req).admitted
    sched.loop.run(until=sched.loop.now + seconds)


class TestIdleClock:
    def test_split_fills_the_window_minus_busy_on_the_sim(self):
        sched = DeepRT(_table())
        req = Request(category=CAT, period=0.15, n_frames=12,
                      relative_deadline=0.4)
        assert sched.submit_request(req).admitted
        sched.run(until=5.0)
        held, ready, empty, running = _idle(sched, 5.0)
        busy = sched.device.busy_time
        assert sched.metrics.completed_frames == 12
        assert held + ready + empty == pytest.approx(5.0 - busy, abs=1e-9)
        assert running == pytest.approx(busy, abs=1e-9)
        assert held > 0 and empty > 0

    def test_frame_refused_by_the_early_flush_guard_is_held(self):
        # b = 1 WCET 0.05 s, window 0.1 s: joints at 0.1, 0.2, ... The
        # frame lands at 0.06; 0.06 + 0.05 passes the joint at 0.1, so
        # the guard refuses the early flush and the frame waits for it.
        sched = DeepRT(_table())
        req = Request(category=CAT, period=1.0, n_frames=1,
                      relative_deadline=0.2, start_time=0.06)
        assert sched.submit_request(req).admitted
        sched.run(until=1.0)
        held, ready, empty, running = _idle(sched, 1.0)
        assert sched.metrics.completed_frames == 1
        assert held == pytest.approx(0.04, abs=1e-9)
        assert ready == 0.0
        assert running == pytest.approx(sched.device.busy_time, abs=1e-9)
        assert held + empty + running == pytest.approx(1.0, abs=1e-9)

    def test_live_completion_lag_counts_as_ready(self):
        sched = _live()
        start = sched.worker.idle_clock.since
        _serve_live(sched)
        now = sched.loop.now
        held, ready, empty, running = _idle(sched, now)
        assert sched.metrics.completed_frames == 6
        assert ready > 0
        assert held + ready + empty + running == pytest.approx(now - start, abs=1e-9)

    def test_aggregate_metrics_sum_the_split_over_slices(self):
        cluster = build_sim_cluster(_table, ("s0", "s1"))
        for _ in range(2):
            req = Request(category=CAT, period=0.1, n_frames=10,
                          relative_deadline=0.5)
            assert cluster.submit_request(req)
        cluster.run(until=3.0)
        agg = cluster.aggregate_metrics()
        split = (agg["device_busy_s"] + agg["device_idle_held_s"]
                 + agg["device_idle_ready_s"] + agg["device_idle_empty_s"])
        assert split == pytest.approx(2 * 3.0, abs=1e-9)
        assert agg["dispatches"] == agg["jobs"] > 0
        assert agg["dispatch_host_s"] > 0
        # the virtual loop runs every callback on time
        assert agg["loop_late_s"] == 0.0


def _counterless_window(tracer, until, **stats):
    """A benchmark window as a program without the idle split, loop lag,
    dispatch host time and compile counters leaves it."""
    from types import SimpleNamespace

    subs = [ev.t for ev in tracer.ring if ev.stage == T.DEVICE_SUBMIT]
    table = {"agg": {"open": {}, "close": {}},
             "stats": {"open": {k: 0 for k in stats}, "close": stats}}
    return SimpleNamespace(
        seconds=until, tracer_events=list(tracer.ring),
        recorder=SimpleNamespace(
            window=(0.0, until), decode=[],
            prefill=[SimpleNamespace(t=t - 0.002) for t in subs]),
        agg=table["agg"], stats=table["stats"],
        extra={"lateness": [0.001, 0.003]},
        delta=lambda t, k: table[t]["close"][k] - table[t]["open"][k])


class TestBenchReadersWithoutCounters:
    """The benchmark's readers of these counters read a program that
    lacks them from its ring, the check's recorder and the engine's own
    compile counts, and still give a number."""

    @pytest.mark.parametrize("start", [0.0, 0.06])
    def test_ring_split_matches_the_idle_clock_on_the_sim(self, start):
        from bench.metrics.idle_held_share import ring_split

        tracer = FrameTracer()
        sched = DeepRT(_table())
        sched.attach_tracer(tracer)
        req = Request(category=CAT, period=0.15, n_frames=12,
                      relative_deadline=0.4, start_time=start)
        assert sched.submit_request(req).admitted
        sched.run(until=5.0)
        held, ready, _, _ = _idle(sched, 5.0)
        split = ring_split(_counterless_window(tracer, 5.0))
        assert held > 0
        assert split["held"] == pytest.approx(held, abs=1e-9)
        assert split["ready"] == pytest.approx(ready, abs=1e-9)

    def test_every_reader_gives_a_finite_number(self):
        from bench.harness import metric_reader

        tracer = FrameTracer()
        sched = DeepRT(_table())
        sched.attach_tracer(tracer)
        req = Request(category=CAT, period=0.15, n_frames=12,
                      relative_deadline=0.4)
        assert sched.submit_request(req).admitted
        sched.run(until=5.0)
        win = _counterless_window(tracer, 5.0, prefill_compiles=2,
                                  decode_compiles=1)
        read = {n: metric_reader(n)(win) for n in (
            "idle_held_share", "idle_ready_share", "dispatch_host_us",
            "loop_lag_ms", "window_compiles")}
        assert all(isinstance(v, float) and math.isfinite(v)
                   for v in read.values()), read
        assert read["idle_held_share"] > 0
        assert read["dispatch_host_us"] == pytest.approx(2000.0)
        assert read["loop_lag_ms"] == pytest.approx(2.0)
        assert read["window_compiles"] == 3.0


class TestLoopLag:
    def test_a_sleeping_callback_makes_the_next_one_late(self):
        import time

        loop = WallClock()
        at = loop.now + 0.01
        loop.schedule(at, lambda: time.sleep(0.2))
        loop.schedule(at, lambda: None)
        loop.run()
        assert loop.loop_callbacks == 2
        assert loop.loop_late_s >= 0.2


ENG_MID = "granite-3-2b"


def _engine(**kw):
    from repro.configs.registry import tiny
    from repro.serving.engine import InferenceEngine

    return InferenceEngine({ENG_MID: tiny(ENG_MID)}, **kw)


class TestEngineCounters:
    def test_new_prefill_bucket_and_row_release_count_as_compiles(self):
        e = _engine(max_slots=5)
        n0 = e.stats["xla_compiles"]
        e.execute(ENG_MID, (24,), 3, "prefill")  # bucket 4: first use
        n1 = e.stats["xla_compiles"]
        assert n1 > n0 and e.stats["xla_compile_s"] > 0
        programs = (e.stats["prefill_compiles"], e.stats["decode_compiles"])
        # The arena's first row release compiles; the engine's per-program
        # counters do not see it.
        e.alloc_slots(ENG_MID, 40, 2)
        assert e.stats["xla_compiles"] > n1
        assert (e.stats["prefill_compiles"], e.stats["decode_compiles"]) == programs

    def test_served_programs_have_their_own_names(self):
        import jax
        import jax.numpy as jnp

        from bench.trace import PROGRAM_PREFIX

        seq, k = 16, 2
        e = _engine(max_slots=2, chunk_depth=k)
        params = e.params[ENG_MID]
        arena = e.arena(ENG_MID, seq)
        m = e.max_slots

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        lowered = {
            "jit_run_prefill": e._prefill_fn(ENG_MID, seq, 1).lower(
                params, spec((1, seq), jnp.int32)),
            "jit_run_decode": e._decode_fn(ENG_MID, seq).lower(
                params, arena.cache, spec((m,), jnp.int32), arena.cur,
                arena.active),
            "jit_run_decode_chunk": e._decode_chunk_fn(ENG_MID, seq, k).lower(
                params, arena.cache, spec((k, m), jnp.int32), arena.cur,
                arena.active, spec((k, m), jnp.bool_)),
        }
        for name, low in lowered.items():
            assert f"module @{name} " in low.as_text(), name
            # the benchmark's program-time reader still matches each
            assert name.startswith(PROGRAM_PREFIX["tpu"])

    def test_decode_real_rows_count_token_rows(self):
        from types import SimpleNamespace

        from bench import check as C
        from bench.harness import metric_reader

        seq = 16
        e = _engine(max_slots=4)
        rec = C.Recorder(e, SimpleNamespace(now=0.0), seed=1,
                         window=(-math.inf, math.inf))
        slots = e.alloc_slots(ENG_MID, seq, 3)
        e.reset_stats()
        e.dispatch(ENG_MID, (seq,), 3, "decode", slots=slots,
                   payload={slots[0]: 5}, step_rows=[slots[0]]).wait()
        # two frames of one stream in a window: its row still steps once
        e.dispatch(ENG_MID, (seq,), 3, "decode", slots=slots,
                   step_rows=[slots[0], slots[2], slots[2]]).wait()
        e.dispatch(ENG_MID, (seq,), 3, "decode", slots=slots).wait()
        rec.close()
        assert e.stats["real_rows"] == 1 + 2 + 3
        batch_rows = metric_reader("batch_rows")(SimpleNamespace(recorder=rec))
        assert e.stats["real_rows"] / e.stats["dispatches"] == batch_rows

    def test_prefill_rehearsal_real_rows_match_batch_rows(self):
        """The benchmark's CPU rehearsal of the prefill cell: per window
        dispatch, the engine's ``real_rows`` step equals the rows the
        benchmark's recorder saw, so both give the same ``batch_rows``."""
        from repro.configs.registry import tiny

        from bench import harness
        from bench.tests.rehearse import CPU_PEAKS, CPU_SLOWDOWN, SEED

        cell = harness.load_cell("granite-prefill-camera")
        streams = [dict(g, period_s=g["period_s"] * CPU_SLOWDOWN,
                        deadline_s=g["deadline_s"] * CPU_SLOWDOWN)
                   for g in cell.mix["streams"]]
        cell.mix = dict(cell.mix, warmup_s=1.0, streams=streams)
        steps = []

        def count_rows(stack):
            engine, loop = stack.engine, stack.cluster.loop
            inner = engine.dispatch

            def dispatch(*args, **kw):
                before = engine.stats["real_rows"]
                handle = inner(*args, **kw)
                steps.append((loop.now, engine.stats["real_rows"] - before))
                return handle

            engine.dispatch = dispatch

        stack = harness.build(cell, False, tiny(cell.config["program_arch"]),
                              CPU_PEAKS)
        try:
            win = harness.serve(stack, cell.mix, SEED, 2.0, False,
                                fault=count_rows)
        finally:
            stack.close()
        inside = [rows for t, rows in steps if win.recorder.in_window(t)]
        assert inside and len(inside) == len(win.recorder.prefill)
        assert sum(inside) / len(inside) == \
            harness.metric_reader("batch_rows")(win)


def _profiled_spans(tmp_path, run):
    """Run ``run()`` under a CPU ``jax.profiler`` trace; return the host
    spans named ``deeprt.*`` as (name, start_ns, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("deeprt."):
                    spans.append((ev.name, ev.start_ns, dict(ev.stats)))
    return spans


class TestProfilerSpans:
    LOOP_SPANS = {"deeprt.clock", "deeprt.loop.wait", "deeprt.admission",
                  "deeprt.disbatcher.flush", "deeprt.edf.dispatch",
                  "deeprt.device.wait", "deeprt.device.complete"}

    def test_spans_follow_the_tracer(self, tmp_path):
        tracer = FrameTracer()
        traced = _profiled_spans(tmp_path / "on",
                                 lambda: _serve_live(_live(tracer)))
        assert {n for n, _s, _m in traced} >= self.LOOP_SPANS
        assert {n for n, _s, _m in traced} <= set(T.SPANS)
        jobs = {ev.meta["job_id"] for ev in tracer.ring
                if ev.stage == T.EDF_DISPATCH}
        for name in ("deeprt.edf.dispatch", "deeprt.device.wait",
                     "deeprt.device.complete"):
            assert {m["job_id"] for n, _s, m in traced if n == name} == jobs
        untraced = _profiled_spans(tmp_path / "off",
                                   lambda: _serve_live(_live()))
        assert untraced == []

    def test_engine_and_transport_spans(self, tmp_path):
        from repro.ingest.transport import TransportServer

        tracer = FrameTracer()
        e = _engine(max_slots=2)
        e.tracer, e.job_id = tracer, 7
        transport = TransportServer(IngestGateway(build_sim_cluster(_table, ("s0",))))
        transport.tracer = tracer

        def run():
            e.execute(ENG_MID, (16,), 1, "prefill")
            transport.datagram(b"not a datagram")

        spans = _profiled_spans(tmp_path, run)
        got = {(n, m.get("job_id")) for n, _s, m in spans}
        assert got == {("deeprt.engine.stage", 7), ("deeprt.engine.launch", 7),
                       ("deeprt.transport.datagram", None)}

    def test_clock_anchor_maps_ring_times_onto_span_starts(self, tmp_path):
        tracer = FrameTracer()
        sched = _live(tracer)
        spans = _profiled_spans(tmp_path, lambda: _serve_live(sched, n_frames=8))
        anchors = [(s, m["loop_now"]) for n, s, m in spans if n == "deeprt.clock"]
        assert tracer.snapshot()["anchor"] == tracer.anchor == anchors[-1][1]
        anchor_ns = anchors[-1][0]
        flush = {m["job_id"]: s for n, s, m in spans
                 if n == "deeprt.disbatcher.flush"}
        closes = {ev.meta["job_id"]: ev.t for ev in tracer.ring
                  if ev.stage == T.WINDOW_CLOSE}
        assert closes and set(closes) == set(flush)
        errors = sorted(abs(tracer.trace_ns(t, anchor_ns) - flush[j])
                        for j, t in closes.items())
        assert errors[len(errors) // 2] < 1e5  # ns: 0.1 ms
        doc = tracer.chrome_trace(anchor_ns=anchor_ns)
        first = tracer.ring[0]
        assert doc["traceEvents"][0]["ts"] == pytest.approx(
            tracer.trace_ns(first.t, anchor_ns) / 1e3)

    def test_untraced_hot_path_builds_no_annotation(self, monkeypatch):
        import jax.profiler

        built = []

        class Counting(jax.profiler.TraceAnnotation):
            def __init__(self, name, **meta):
                built.append(name)
                super().__init__(name, **meta)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        _serve_live(_live())
        assert built == []
        _serve_live(_live(FrameTracer()))
        assert set(built) >= self.LOOP_SPANS
