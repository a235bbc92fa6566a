"""Network transport front end: codec, LinkPlan chaos, reassembly,
flow control, re-homing, completion faults, and the UDP binding.

Covers the acceptance bars of the transport PR:

- the wire codec round-trips DATA and control messages bit-exactly;
- ``LinkPlan.from_seed`` is deterministic and prefix-stable (the
  FaultPlan property, on the wire);
- reassembly survives loss/duplication/reordering/delay: frames are
  delivered in order exactly once, duplicates are suppressed, frames
  the link destroyed are declared lost, and the conservation identity
  ``completed + dropped + lost == ingested`` extends through the
  transport (plus the wire-level identity: every datagram that reached
  the server lands in exactly one bucket);
- client-signaled backpressure: under a 2x burst overload the
  flow-control arm (credit/duty downshift at the source) achieves a
  strictly lower effective miss rate than the no-flow-control arm, and
  the downshift is observable on the StreamSession;
- session re-homing: failing a session's home slice re-admits its tail
  as an EXTERNAL request and the transport replays REAL buffered bytes
  into the new slice — post-failover deliveries are bit-identical to
  the source's payloads (never zeros);
- duplicated / reordered COMPLETION signals (device-side network
  faults) are tolerated: no double-counted frames, no double-released
  leases, ``Metrics.duplicate_completions`` counts the suppressions;
- a hypothesis property: for ANY seed-derived link schedule (with or
  without a mid-stream slice failure), in-order exactly-once delivery,
  bit-exact payloads, and both conservation identities hold.
"""
import json

import numpy as np
import pytest

from repro.core import (
    DUP_COMPLETE,
    REORDER_COMPLETE,
    Category,
    DeepRT,
    EventLoop,
    FaultPlan,
    FaultSpec,
    FaultyDevice,
    ProfileTable,
    Request,
    SequentialDevice,
    WallClock,
)
from repro.core.cluster import build_sim_cluster
from repro.ingest import (
    DROP,
    DUPLICATE,
    LINK_DELAY,
    REORDER,
    BurstSource,
    IngestGateway,
    LinkFault,
    LinkPlan,
    PeriodicSource,
    SimLink,
    TransportServer,
    TransportSource,
    UdpClientLink,
    UdpServerBinding,
)
from repro.ingest.transport import (
    CREDIT,
    DATA,
    FIN,
    STATUS,
    STATUS_REPLY,
    decode,
    encode_control,
    encode_data,
)

CAT = Category("m", (4,))


def _sim_table(a: float = 0.01, c: float = 0.04) -> ProfileTable:
    table = ProfileTable()
    for b in (1, 2, 4, 8, 16, 32):
        table.record("m", (4,), b, a + c * b)
    return table


def _cluster(loop, names=("s0", "s1")):
    return build_sim_cluster(_sim_table, list(names), loop=loop)


def _pipeline(loop, plan=None, names=("s0", "s1"), flow=True, **server_kw):
    cluster = _cluster(loop, names)
    gateway = IngestGateway(cluster)
    server = TransportServer(
        gateway, flow_control=flow, record_payloads=True, **server_kw
    )
    link = SimLink(loop, server.datagram, plan=plan)
    return cluster, server, link


def _drain(loop, server):
    loop.run()
    server.finalize_all()
    loop.run()


def _conserved(cluster) -> bool:
    agg = cluster.aggregate_metrics()
    total = (
        agg["completed_frames"] + agg["dropped_frames"] + agg["lost_frames"]
    )
    return total == agg["ingested_frames"]


# ---------------------------------------------------------------------------
# Wire codec
# ---------------------------------------------------------------------------


class TestCodec:
    def test_data_roundtrip_bit_exact(self):
        payload = np.arange(12, dtype=np.int32).reshape(3, 4) - 5
        blob = encode_data(7, 42, 1.25, payload)
        mtype, msg = decode(blob)
        assert mtype == DATA
        assert (msg.session_id, msg.seq, msg.sent_at) == (7, 42, 1.25)
        assert msg.payload.dtype == np.int32
        assert np.array_equal(msg.payload, payload)

    def test_scalar_payload_roundtrip(self):
        blob = encode_data(1, 0, 0.0, np.int32(9))
        _mtype, msg = decode(blob)
        assert msg.payload.shape == ()
        assert int(msg.payload) == 9

    def test_control_roundtrip(self):
        blob = encode_control(FIN, {"sid": 3, "total": 17})
        mtype, body = decode(blob)
        assert mtype == FIN
        assert body == {"sid": 3, "total": 17}

    def test_bad_magic_rejected(self):
        from repro.ingest.transport import MALFORMED

        mtype, reason = decode(b"NOPE" + bytes(16))
        assert mtype == MALFORMED
        assert reason == "bad_magic"


# ---------------------------------------------------------------------------
# LinkPlan
# ---------------------------------------------------------------------------


class TestLinkPlan:
    def test_from_seed_deterministic(self):
        kw = dict(p_drop=0.1, p_dup=0.1, p_reorder=0.2, p_delay=0.2)
        a = LinkPlan.from_seed(9, 200, **kw)
        b = LinkPlan.from_seed(9, 200, **kw)
        assert [(s.kind, s.at_send, s.delay) for s in a.specs] == [
            (s.kind, s.at_send, s.delay) for s in b.specs
        ]
        c = LinkPlan.from_seed(10, 200, **kw)
        assert [(s.kind, s.at_send) for s in a.specs] != [
            (s.kind, s.at_send) for s in c.specs
        ]

    def test_from_seed_prefix_stable(self):
        kw = dict(p_drop=0.15, p_dup=0.15, p_reorder=0.15, p_delay=0.15)
        short = LinkPlan.from_seed(4, 50, **kw)
        long = LinkPlan.from_seed(4, 500, **kw)
        for i in range(50):
            a, b = short.for_send(i), long.for_send(i)
            assert (a is None) == (b is None)
            if a is not None:
                assert (a.kind, a.delay, a.copies) == (b.kind, b.delay, b.copies)

    def test_arrivals_semantics(self):
        plan = LinkPlan((
            LinkFault(DROP, 0),
            LinkFault(DUPLICATE, 1, copies=3),
            LinkFault(REORDER, 2, delay=0.5),
            LinkFault(LINK_DELAY, 3, delay=0.01),
        ))
        assert plan.arrivals(0) == []
        assert plan.arrivals(1) == [0.0, 0.0, 0.0]
        assert plan.arrivals(2) == [0.5]
        assert plan.arrivals(3) == [0.01]
        assert plan.arrivals(4) == [0.0]  # clean send

    def test_validation(self):
        with pytest.raises(ValueError):
            LinkFault("gremlin", 0)
        with pytest.raises(ValueError):
            LinkFault(REORDER, 0, delay=0.0)
        with pytest.raises(ValueError):
            LinkFault(DUPLICATE, 0, copies=1)
        with pytest.raises(ValueError):
            LinkPlan((LinkFault(DROP, 2), LinkFault(DROP, 2)))
        with pytest.raises(ValueError):
            LinkPlan.from_seed(0, 10, p_drop=0.6, p_dup=0.6)


# ---------------------------------------------------------------------------
# Reassembly over a chaotic link (sim time)
# ---------------------------------------------------------------------------


class TestReassembly:
    def _run(self, plan, n_frames=24, period=0.5, deadline=2.0, **server_kw):
        loop = EventLoop()
        cluster, server, link = _pipeline(loop, plan=plan, **server_kw)
        src = PeriodicSource(
            period=period, n_frames=n_frames, payload_shape=(4,), seed=7
        )
        client = TransportSource(src, CAT, deadline, link)
        assert client.start(server)
        _drain(loop, server)
        return cluster, server, server.sessions[1], src, client

    def test_lossless_link_delivers_everything_in_order(self):
        cluster, _server, ts, src, _client = self._run(None, n_frames=16)
        assert ts.delivered == 16
        assert ts.delivered_log == list(range(16))
        assert ts.net_lost == 0 and ts.duplicates == 0
        for seq, payload in ts.delivered_payloads.items():
            assert np.array_equal(payload, src.payload(seq))
        assert _conserved(cluster)
        assert ts.wire_conserved()

    def test_duplicates_suppressed_exactly_once(self):
        plan = LinkPlan((
            LinkFault(DUPLICATE, 2, copies=4),
            LinkFault(DUPLICATE, 5, copies=2),
        ))
        cluster, _server, ts, _src, _client = self._run(plan, n_frames=10)
        assert ts.delivered == 10
        assert ts.delivered_log == list(range(10))
        assert ts.duplicates == 4  # 3 extra copies + 1 extra copy
        assert _conserved(cluster) and ts.wire_conserved()

    def test_drops_declared_lost_and_conserved(self):
        plan = LinkPlan((LinkFault(DROP, 3), LinkFault(DROP, 8)))
        cluster, _server, ts, _src, _client = self._run(plan, n_frames=12)
        assert ts.delivered == 10
        assert ts.net_lost == 2
        assert 3 not in ts.delivered_log and 8 not in ts.delivered_log
        assert ts.delivered_log == sorted(ts.delivered_log)
        assert ts.session.frames_lost == 2
        assert _conserved(cluster) and ts.wire_conserved()

    def test_reordered_frame_held_then_delivered_in_order(self):
        # Frame 4 is held 0.6s: frames 5 and 6 arrive first and must wait
        # in the reorder buffer; delivery order stays monotone.
        plan = LinkPlan((LinkFault(REORDER, 4, delay=0.6),))
        cluster, _server, ts, src, _client = self._run(plan, n_frames=12)
        assert ts.delivered == 12
        assert ts.delivered_log == list(range(12))
        for seq, payload in ts.delivered_payloads.items():
            assert np.array_equal(payload, src.payload(seq))
        assert _conserved(cluster) and ts.wire_conserved()

    def test_reorder_window_overflow_skips_gap(self):
        # Frame 1 held far beyond the stream: with a tiny window the gap
        # is skipped (frame 1 lost), later frames still deliver in order,
        # and the straggler is refused/suppressed when it finally lands.
        plan = LinkPlan((LinkFault(REORDER, 1, delay=30.0),))
        cluster, _server, ts, _src, _client = self._run(
            plan, n_frames=10, reorder_window=2, reorder_timeout=0.9
        )
        assert 1 not in ts.delivered_log
        assert ts.delivered_log == sorted(ts.delivered_log)
        assert ts.net_lost >= 1
        assert _conserved(cluster) and ts.wire_conserved()

    def test_late_frame_rejected_against_deadline(self):
        # Held for 3x the relative deadline: the frame would miss even on
        # an idle device, so it is rejected at the door as a drop.
        plan = LinkPlan((LinkFault(LINK_DELAY, 2, delay=6.0),))
        cluster, _server, ts, _src, _client = self._run(
            plan, n_frames=8, deadline=2.0, reorder_timeout=8.0
        )
        assert ts.late_rejected == 1
        assert 2 not in ts.delivered_log
        assert ts.session.frames_dropped >= 1
        assert ts.session.last_shed_reason.startswith("late")
        # Resolved once, as a drop: its seq is not declared lost too.
        assert ts.net_lost == 0
        assert cluster.aggregate_metrics()["ingested_frames"] == 8
        assert _conserved(cluster) and ts.wire_conserved()

    def test_late_frame_in_order_does_not_stall_the_tail(self):
        # Frame 2 arrives in order but past its deadline budget (sent at
        # 6.0, lands at 7.5, deadline 1.0); frame 3 lands on time at 9.0
        # and is delivered then, not held for a reorder timeout behind
        # the rejected seq.
        plan = LinkPlan((LinkFault(LINK_DELAY, 2, delay=1.5),))
        cluster, _server, ts, _src, _client = self._run(
            plan, n_frames=6, period=3.0, deadline=1.0
        )
        assert ts.late_rejected == 1 and ts.net_lost == 0
        assert ts.delivered_log == [0, 1, 3, 4, 5]
        assert cluster.aggregate_metrics()["ingested_frames"] == 6
        sl = cluster.slices[ts.session.slice_name]
        arrivals = {
            idx: arrival
            for (_rid, idx), (arrival, _dl, _done)
            in sl.scheduler.metrics.frame_records.items()
        }
        assert arrivals[3] == pytest.approx(9.0)
        assert _conserved(cluster) and ts.wire_conserved()

    def test_deliveries_are_deadline_stamped_at_arrival(self):
        # A LINK_DELAY inside the deadline budget still delivers; its
        # frame is stamped at ARRIVAL, so the extra wire latency does not
        # eat scheduling slack twice.
        plan = LinkPlan((LinkFault(LINK_DELAY, 0, delay=0.2),))
        loop = EventLoop()
        cluster, server, link = _pipeline(loop, plan=plan)
        src = PeriodicSource(period=0.5, n_frames=4, payload_shape=(4,), seed=1)
        client = TransportSource(src, CAT, 2.0, link)
        assert client.start(server)
        _drain(loop, server)
        sl = cluster.slices[server.sessions[1].session.slice_name]
        records = sl.scheduler.metrics.frame_records
        assert records and all(
            deadline == pytest.approx(arrival + 2.0)
            for arrival, deadline, _completion in records.values()
        )


# ---------------------------------------------------------------------------
# Flow control (client-signaled backpressure)
# ---------------------------------------------------------------------------


class TestFlowControl:
    def _overloaded(self, flow: bool):
        loop = EventLoop()
        cluster, server, link = _pipeline(loop, names=("s0",), flow=flow)
        src = BurstSource(
            period=0.12, n_frames=120, payload_shape=(4,), seed=3,
            burst=8, duty=0.4,
        )
        client = TransportSource(src, CAT, 0.36, link, flow_control=flow)
        assert client.start(server)
        _drain(loop, server)
        m = cluster.slices["s0"].scheduler.metrics
        eff = (
            m.missed_frames + m.dropped_frames + m.lost_frames
        ) / m.ingested_frames
        return cluster, server.sessions[1], client, eff

    def test_flow_control_strictly_beats_control_arm(self):
        _c1, ts_a, client_a, eff_a = self._overloaded(flow=True)
        _c2, ts_b, client_b, eff_b = self._overloaded(flow=False)
        assert eff_a < eff_b
        # The downshift actually happened, at the source.
        assert client_a.downshifts_applied > 0
        assert client_a.duty > client_a.plan_duty
        assert client_b.duty == client_b.plan_duty

    def test_downshift_observable_on_session(self):
        _cluster_, ts, _client, _eff = self._overloaded(flow=True)
        s = ts.session
        assert s.downshifts > 0
        assert s.credit < 1.0  # stretched below the plan's burst rate
        assert "over_budget" in s.last_downshift_reason
        assert _conserved(_cluster_) and ts.wire_conserved()

    def test_control_arm_client_ignores_credit(self):
        _cluster_, ts, client, _eff = self._overloaded(flow=False)
        assert client.credits_seen == 0  # server never sent any
        assert ts.session.downshifts == 0


# ---------------------------------------------------------------------------
# Session re-homing on slice failover
# ---------------------------------------------------------------------------


class TestRehoming:
    def _failover_run(self, fail_at=7.0, n_frames=30, plan=None):
        loop = EventLoop()
        cluster, server, link = _pipeline(loop, plan=plan)
        src = PeriodicSource(
            period=0.5, n_frames=n_frames, payload_shape=(4,), seed=11
        )
        client = TransportSource(src, CAT, 2.0, link)
        assert client.start(server)
        ts = server.sessions[1]
        home = ts.session.slice_name
        loop.schedule(fail_at, lambda: cluster.fail_slice(home), priority=0)
        _drain(loop, server)
        return cluster, server, ts, src, client, home

    def test_session_rehomes_with_real_payload(self):
        cluster, _server, ts, src, client, home = self._failover_run()
        assert ts.rehomes == 1
        assert ts.session.rehomes == 1
        assert ts.session.slice_name != home
        assert client.rehomes_seen == 1
        post = [s for s in ts.delivered_log if s >= 15]
        assert post, "no post-failover deliveries"
        for seq in post:
            payload = ts.delivered_payloads[seq]
            assert payload.any(), f"post-failover frame {seq} is zeros"
            assert np.array_equal(payload, src.payload(seq))
        assert _conserved(cluster) and ts.wire_conserved()

    def test_rehomed_tail_is_external_not_synthetic(self):
        cluster, _server, ts, _src, _client, _home = self._failover_run()
        tail_rid = ts.session.request_id
        new_slice = cluster.slices[ts.session.slice_name]
        # Synthetic re-admission would stream payload-less frames; every
        # frame the new slice completed for the tail carries real bytes.
        tail_frames = [
            f
            for job in new_slice.scheduler.worker.completed_jobs
            for f in job.frames
            if f.request_id == tail_rid
        ]
        assert tail_frames
        assert all(f.payload is not None for f in tail_frames)
        assert all(np.asarray(f.payload).any() for f in tail_frames)

    def test_rehome_under_chaotic_link(self):
        plan = LinkPlan.from_seed(
            21, 60, p_drop=0.08, p_dup=0.08, p_reorder=0.1,
            reorder_hold=(0.1, 0.5),
        )
        cluster, _server, ts, src, _client, _home = self._failover_run(
            plan=plan
        )
        assert ts.rehomes == 1
        assert ts.delivered_log == sorted(set(ts.delivered_log))
        for seq, payload in ts.delivered_payloads.items():
            assert np.array_equal(payload, src.payload(seq))
        assert _conserved(cluster) and ts.wire_conserved()

    def test_no_surviving_slice_expires_session(self):
        # Single-slice cluster: failover has nowhere to re-home; the
        # parked tail expires and the session closes, stragglers refused.
        loop = EventLoop()
        cluster, server, link = _pipeline(loop, names=("s0",))
        src = PeriodicSource(period=0.5, n_frames=20, payload_shape=(4,), seed=2)
        client = TransportSource(src, CAT, 2.0, link)
        assert client.start(server)
        ts = server.sessions[1]
        loop.schedule(4.0, lambda: cluster.fail_slice("s0"), priority=0)
        _drain(loop, server)
        assert ts.session.state == "closed"
        assert ts.rehomes == 0
        assert cluster.parked_expired == [ts.session.request_id] or ts.finalized
        assert ts.wire_conserved()


# ---------------------------------------------------------------------------
# Status snapshot (observability)
# ---------------------------------------------------------------------------


class TestStatusSnapshot:
    def test_snapshot_is_json_and_complete(self):
        loop = EventLoop()
        cluster, server, link = _pipeline(loop)
        src = PeriodicSource(period=0.5, n_frames=10, payload_shape=(4,), seed=5)
        client = TransportSource(src, CAT, 2.0, link)
        assert client.start(server)
        home = server.sessions[1].session.slice_name
        loop.schedule(2.2, lambda: cluster.fail_slice(home), priority=0)
        _drain(loop, server)
        snap = json.loads(server.status_json())
        assert set(snap["slices"]) == {"s0", "s1"}
        sess = snap["sessions"]["1"]
        assert sess["wire"]["conserved"] is True
        assert sess["rehomes"] == 1
        assert sess["gateway"]["ingested"] == sess["wire"]["delivered"] + sess["wire"]["shed"] + sess["wire"]["late_rejected"] + sess["wire"]["lost_to_slice"]
        # Health transitions observed through the transport's own
        # subscription (quarantine of the failed slice).
        assert any(
            t["slice"] == home and t["new"] == "quarantined"
            for t in snap["health_transitions"]
        )
        assert snap["slices"][home]["alive"] is False


# ---------------------------------------------------------------------------
# Device-side completion faults (satellite: faults.py + EDF tolerance)
# ---------------------------------------------------------------------------


class TestCompletionFaults:
    def _run_with(self, plan: FaultPlan, n_frames=12):
        loop = EventLoop()
        device = FaultyDevice(SequentialDevice(loop), plan)
        sched = DeepRT(_sim_table(), device=device, loop=loop)
        req = Request(
            category=CAT, period=0.5, relative_deadline=1.5,
            n_frames=n_frames, start_time=0.0,
        )
        assert sched.submit_request(req).admitted
        loop.run()
        return sched.metrics

    def test_duplicate_completion_not_double_counted(self):
        m = self._run_with(FaultPlan((FaultSpec(DUP_COMPLETE, 1),)))
        assert m.completed_frames == 12
        assert m.duplicate_completions == 1
        assert m.completed_frames + m.dropped_frames + m.lost_frames == m.ingested_frames

    def test_reordered_completion_tolerated(self):
        # Job 3's signal is deferred past later jobs' signals; nothing
        # crashes, nothing double-counts, every frame resolves once.
        m = self._run_with(
            FaultPlan((FaultSpec(REORDER_COMPLETE, 3, factor=6.0),))
        )
        assert m.completed_frames == 12
        assert m.duplicate_completions == 0
        assert m.completed_frames + m.dropped_frames + m.lost_frames == m.ingested_frames

    def test_mixed_completion_chaos_conserves(self):
        plan = FaultPlan.from_seed(
            13, 64, p_dup_complete=0.2, p_reorder_complete=0.2,
        )
        m = self._run_with(plan, n_frames=40)
        assert m.completed_frames == 40
        assert m.duplicate_completions >= 1
        assert m.completed_frames + m.dropped_frames + m.lost_frames == m.ingested_frames

    def test_from_seed_draws_new_kinds(self):
        plan = FaultPlan.from_seed(
            3, 400, p_dup_complete=0.25, p_reorder_complete=0.25,
        )
        kinds = {s.kind for s in plan.specs}
        assert DUP_COMPLETE in kinds and REORDER_COMPLETE in kinds
        again = FaultPlan.from_seed(
            3, 400, p_dup_complete=0.25, p_reorder_complete=0.25,
        )
        assert [(s.kind, s.at_submit) for s in plan.specs] == [
            (s.kind, s.at_submit) for s in again.specs
        ]

    def test_reorder_complete_spec_must_defer(self):
        with pytest.raises(ValueError):
            FaultSpec(REORDER_COMPLETE, 0, factor=1.0, extra=0.0)


# ---------------------------------------------------------------------------
# UDP binding (live WallClock path, loopback socket)
# ---------------------------------------------------------------------------


class TestUdpBinding:
    def test_udp_roundtrip_over_loopback(self):
        import threading
        import time

        loop = WallClock()
        sched = DeepRT(
            _sim_table(0.001, 0.002), device=SequentialDevice(loop), loop=loop
        )
        gateway = IngestGateway(sched)
        server = TransportServer(gateway, record_payloads=True)
        binding = UdpServerBinding(server).start()
        link = UdpClientLink(loop, binding.addr)
        # The loop runs on its own thread, held alive while datagrams are
        # in flight (the rx threads post work into it, same protocol as
        # AsyncDevice completions).
        loop.hold()
        runner = threading.Thread(target=loop.run, daemon=True)
        runner.start()
        try:
            src = PeriodicSource(
                period=0.02, n_frames=8, payload_shape=(4,), seed=9
            )
            client = TransportSource(src, CAT, 1.0, link)
            sid, ok = link.handshake(client)
            assert ok and sid == 1
            client.start_remote(sid)
            deadline = time.time() + 10.0
            while time.time() < deadline:
                ts = server.sessions.get(sid)
                if ts is not None and len(ts.seen) >= 8:
                    break
                time.sleep(0.02)
            loop.post(server.finalize_all)
            while time.time() < deadline and not server.sessions[sid].finalized:
                time.sleep(0.02)
            ts = server.sessions[sid]
            assert ts.finalized
            assert ts.delivered == 8
            assert ts.delivered_log == list(range(8))
            for seq, payload in ts.delivered_payloads.items():
                assert np.array_equal(payload, src.payload(seq))
            assert ts.wire_conserved()
            m = sched.metrics
            assert (
                m.completed_frames + m.dropped_frames + m.lost_frames
                == m.ingested_frames
            )
        finally:
            link.close()
            binding.close()
            loop.release()
            runner.join(timeout=2.0)

    def test_udp_status_probe(self):
        import socket as socket_mod

        loop = WallClock()
        sched = DeepRT(_sim_table(), device=SequentialDevice(loop), loop=loop)
        server = TransportServer(IngestGateway(sched))
        binding = UdpServerBinding(server).start()
        probe = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
        probe.settimeout(2.0)
        try:
            probe.sendto(encode_control(STATUS, {}), binding.addr)
            data, _addr = probe.recvfrom(65535)
            mtype, body = decode(data)
            assert mtype == STATUS_REPLY
            assert "sessions" in body and "scheduler" in body
        finally:
            probe.close()
            binding.close()


# ---------------------------------------------------------------------------
# Hypothesis property: any chaos schedule, same guarantees (satellite d)
# ---------------------------------------------------------------------------


def _chaos_run(seed, p_drop, p_dup, p_reorder, p_delay, fail):
    loop = EventLoop()
    cluster, server, link = _pipeline(loop)
    link.plan = LinkPlan.from_seed(
        seed, 80,
        p_drop=p_drop, p_dup=p_dup, p_reorder=p_reorder, p_delay=p_delay,
        reorder_hold=(0.1, 0.6),
    )
    src = PeriodicSource(period=0.5, n_frames=24, payload_shape=(4,), seed=seed)
    client = TransportSource(src, CAT, 2.0, link)
    assert client.start(server)
    ts = server.sessions[1]
    if fail:
        home = ts.session.slice_name
        loop.schedule(5.0, lambda: cluster.fail_slice(home), priority=0)
    _drain(loop, server)
    # In-order, exactly-once delivery.
    assert ts.delivered_log == sorted(set(ts.delivered_log))
    # Bit-identical to the lossless replay of the surviving frames
    # (re-homed or not, delivered bytes are the source's bytes).
    for seq, payload in ts.delivered_payloads.items():
        assert np.array_equal(payload, src.payload(seq))
    # Conservation through the transport, and on the wire.
    assert _conserved(cluster)
    assert ts.wire_conserved()
    # Every wire frame resolved to exactly one terminal outcome.
    assert ts.finalized or ts.session.state in ("closed", "failover")


class TestLinkChaosProperty:
    @pytest.mark.slow
    def test_any_schedule_preserves_guarantees(self):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis (installed in CI); a bare "
            "env skips instead of erroring at collection",
        )
        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        @settings(
            max_examples=30,
            deadline=None,
            suppress_health_check=[HealthCheck.too_slow],
        )
        @given(
            seed=st.integers(0, 10_000),
            p_drop=st.floats(0.0, 0.2),
            p_dup=st.floats(0.0, 0.2),
            p_reorder=st.floats(0.0, 0.2),
            p_delay=st.floats(0.0, 0.2),
            fail=st.booleans(),
        )
        def prop(seed, p_drop, p_dup, p_reorder, p_delay, fail):
            _chaos_run(seed, p_drop, p_dup, p_reorder, p_delay, fail)

        prop()

    def test_chaos_run_without_hypothesis(self):
        # Deterministic spot-checks of the same property, so the
        # guarantees are still exercised in environments without
        # hypothesis (the property above fuzzes the same runner).
        for seed, fail in ((0, False), (17, True), (91, True)):
            _chaos_run(
                seed, p_drop=0.12, p_dup=0.1, p_reorder=0.15, p_delay=0.1,
                fail=fail,
            )
