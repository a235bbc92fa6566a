"""Streaming ingestion gateway: sources, sessions, staging rings,
payload fidelity, and adaptation-driven load shedding.

Covers the acceptance bars of the ingest PR:

- FrameSource plans are deterministic (bit-identical payloads and
  offsets across re-materializations and processes) and respect their
  shape contracts (camera jitter bounded and order-preserving; burst
  duty compresses the same frame budget into 1/duty of the time; trace
  replay is strict-periodic at the trace's sampled period);
- StagingRing cycles a FIXED host scratch pool (zero fresh host
  allocations after construction) and never lets job N's staged bytes
  be observed by job N+1's fill (double-buffer isolation — including a
  hypothesis interleaving property);
- end-to-end payload fidelity: engine outputs are bit-identical to a
  dense reference consuming the same ingested bytes, and DIFFER when
  the bytes differ — the synthetic-zeros path is gone;
- zero decode recompiles across a staged 1 -> max_slots -> 1 sweep with
  real payloads;
- the gateway's lifecycle (register -> admit/place -> stream -> close)
  runs identically over a simulated DeepRT and the live cluster path,
  deadline-stamping at arrival;
- under a 2x bursty overload, adaptation-driven shedding yields strictly
  fewer deadline misses than no shedding, and every dropped frame is
  accounted (ingested == delivered + dropped, completed + dropped ==
  ingested in Metrics).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import tiny
from repro.core import Category, DeepRT, ProfileTable, Request
from repro.ingest import (
    BurstSource,
    CameraSource,
    IngestGateway,
    ShedPolicy,
    StagingRing,
    TraceSource,
)
from repro.core.traces import TraceSpec
from repro.launch.checks import LOGIT_RTOL, compare_logits
from repro.models import model_for
from repro.serving.engine import InferenceEngine

MID = "granite-3-2b"
SEQ = 16
SEQ_D = 8


def _logits_match(got, ref) -> bool:
    """An arena row (batch max_slots) vs a batch-1 reference runs the
    same f32 math in another reduction order (XLA picks it by batch
    shape): ulp-level gaps (2e-7 on logits of ~0.4 observed). Compare
    under the tolerance and argmax rule of ``repro.launch.checks``.
    The other comparisons in this file stay exact."""
    return compare_logits(got, ref, LOGIT_RTOL["float32"])["ok"]


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------


class TestSources:
    def test_plan_is_deterministic_and_rematerializable(self):
        a = CameraSource(period=0.1, n_frames=12, payload_shape=(SEQ,), seed=7)
        b = CameraSource(period=0.1, n_frames=12, payload_shape=(SEQ,), seed=7)
        pa, pb = a.plan(), b.plan()
        assert [f.offset for f in pa] == [f.offset for f in pb]
        for fa, fb in zip(pa, pb):
            assert np.array_equal(fa.payload, fb.payload)
        # Re-materializing the SAME source yields the same plan (no
        # hidden iteration state).
        assert [f.offset for f in a.plan()] == [f.offset for f in pa]

    def test_different_seeds_differ(self):
        a = CameraSource(period=0.1, n_frames=8, payload_shape=(SEQ,), seed=1)
        b = CameraSource(period=0.1, n_frames=8, payload_shape=(SEQ,), seed=2)
        assert any(
            not np.array_equal(x.payload, y.payload)
            for x, y in zip(a.plan(), b.plan())
        )

    def test_camera_jitter_bounded_and_ordered(self):
        src = CameraSource(
            period=0.1, n_frames=50, jitter_frac=0.5, payload_shape=(), seed=3
        )
        offs = [f.offset for f in src.plan()]
        assert offs == sorted(offs)
        assert all(o >= 0 for o in offs)
        half = 0.5 * 0.1 / 2
        assert all(abs(o - i * 0.1) <= half + 1e-12 for i, o in enumerate(offs))
        # Jitter actually present (not silently periodic).
        assert any(abs(o - i * 0.1) > 1e-6 for i, o in enumerate(offs))

    def test_burst_duty_compresses_arrivals(self):
        declared = BurstSource(
            period=0.1, n_frames=20, burst=4, duty=1.0, payload_shape=(), seed=0
        )
        overload = BurstSource(
            period=0.1, n_frames=20, burst=4, duty=0.5, payload_shape=(), seed=0
        )
        span_full = declared.plan()[-1].offset
        span_half = overload.plan()[-1].offset
        # Same frame budget in ~half the time: 2x instantaneous rate.
        assert span_half == pytest.approx(span_full * 0.5, rel=0.1)
        # The declared (admission-visible) rate is unchanged.
        assert overload.period == declared.period

    def test_trace_source_replays_trace_request(self):
        spec = TraceSpec(
            mean_period=0.2, mean_deadline=0.4, n_requests=3,
            models=(MID,), shapes=((SEQ,),), seed=5,
        )
        pairs = TraceSource.from_trace(spec, payload_shape=(SEQ,))
        assert len(pairs) == 3
        for req, src in pairs:
            assert src.period == req.period
            assert src.n_frames == req.n_frames
            offs = [f.offset for f in src.plan()]
            assert offs == pytest.approx(
                [i * req.period for i in range(req.n_frames)]
            )

    def test_validation(self):
        with pytest.raises(ValueError, match="period"):
            CameraSource(period=0.0, n_frames=5)
        with pytest.raises(ValueError, match="jitter"):
            CameraSource(period=0.1, n_frames=5, jitter_frac=1.5)
        with pytest.raises(ValueError, match="duty"):
            BurstSource(period=0.1, n_frames=5, duty=0.0)


# ---------------------------------------------------------------------------
# Staging ring
# ---------------------------------------------------------------------------


class TestStagingRing:
    def test_depth_validated(self):
        with pytest.raises(ValueError, match="depth"):
            StagingRing((4,), depth=1)

    def test_fixed_scratch_pool_cycles(self):
        ring = StagingRing((2, 4), depth=3)
        seen = []
        for _ in range(7):
            ring.stage(lambda buf: seen.append(id(buf)))
        # Round-robin over exactly ``depth`` buffers, allocated once.
        assert len(set(seen)) == 3
        assert seen[:3] == seen[3:6]
        assert ring.host_allocs == 3
        assert ring.fills == 7
        assert ring.bytes_staged == 7 * ring.frame_nbytes

    def test_consecutive_fills_use_distinct_buffers(self):
        """Fill buffer B while the in-flight job reads A: jobs N and N+1
        never share a scratch buffer."""
        ring = StagingRing((4,), depth=2)
        ids = []
        for _ in range(4):
            ring.stage(lambda buf: ids.append(id(buf)))
        assert all(a != b for a, b in zip(ids, ids[1:]))

    def test_stage_rows_pads_and_validates(self):
        ring = StagingRing((4, 3), depth=2)
        out = ring.stage_rows(np.ones((2, 3), np.int32), 2)
        arr = np.asarray(out)
        assert arr[:2].tolist() == np.ones((2, 3)).tolist()
        assert (arr[2:] == 0).all()
        with pytest.raises(ValueError, match="payload shape"):
            ring.stage_rows(np.ones((2, 5), np.int32), 2)
        with pytest.raises(ValueError, match="n_rows"):
            ring.stage_rows(None, 9)

    def test_wrong_dtype_payload_rejected(self):
        """Float bytes handed to an int token ring must fail at the
        boundary, not stage truncated garbage."""
        ring = StagingRing((4, 3), depth=2)
        with pytest.raises(ValueError, match="dtype"):
            ring.stage_rows(np.ones((2, 3), np.float32), 2)
        # Same-kind integer casts are fine.
        ring.stage_rows(np.ones((2, 3), np.int64), 2)

    def test_staged_bytes_correct_within_ring_window(self):
        """A staged array read before its scratch is refilled carries
        exactly the ingested bytes (uploads may alias host memory, so
        this holds only within the depth-1 window — the consumer guard
        enforces the window)."""
        ring = StagingRing((4,), depth=2)
        a = ring.stage_rows(np.full((4,), 1, np.int32), 4)
        b = ring.stage_rows(np.full((4,), 2, np.int32), 4)
        assert np.asarray(a).tolist() == [1, 1, 1, 1]
        assert np.asarray(b).tolist() == [2, 2, 2, 2]

    def test_consumer_guard_runs_before_scratch_reuse(self):
        """Refilling a scratch waits for the job that consumed it: the
        double-buffer correctness mechanism on zero-copy backends."""
        ring = StagingRing((4,), depth=2)
        order = []
        ring.stage(lambda buf: order.append("fill0"))  # scratch 0
        ring.attach_consumer(lambda: order.append("wait0"))
        ring.stage(lambda buf: order.append("fill1"))  # scratch 1
        ring.attach_consumer(lambda: order.append("wait1"))
        ring.stage(lambda buf: order.append("fill2"))  # scratch 0 again
        assert order == ["fill0", "fill1", "wait0", "fill2"]
        assert ring.consumer_waits == 1
        # Guards fire at most once each.
        ring.stage(lambda buf: None)  # scratch 1: wait1 fires
        ring.stage(lambda buf: None)  # scratch 0: no guard left
        assert order[-1] == "wait1"
        assert ring.consumer_waits == 2

    def test_attach_consumer_requires_a_stage(self):
        ring = StagingRing((4,), depth=2)
        with pytest.raises(RuntimeError, match="attach_consumer"):
            ring.attach_consumer(lambda: None)


# ---------------------------------------------------------------------------
# Engine payload fidelity (the no-more-synthetic-zeros bars)
# ---------------------------------------------------------------------------


def _engine(**kw):
    kw.setdefault("max_slots", 4)
    return InferenceEngine({MID: tiny(MID)}, **kw)


class TestPayloadFidelity:
    def test_prefill_bit_identical_to_dense_reference(self):
        e = _engine()
        model = model_for(tiny(MID))
        pay = np.random.default_rng(0).integers(
            0, 64, size=(3, SEQ), dtype=np.int32
        )
        out = e.dispatch(MID, (SEQ,), 3, "prefill", payload=pay).wait()
        logits, _ = jax.jit(model.forward)(e.params[MID], jnp.asarray(pay))
        ref = logits[:, -1].argmax(-1)
        assert bool(jnp.all(out[:3] == ref))

    def test_prefill_output_depends_on_payload(self):
        e = _engine()
        model = model_for(tiny(MID))
        rng = np.random.default_rng(1)
        p1 = rng.integers(0, 64, size=(2, SEQ), dtype=np.int32)
        p2 = p1.copy()
        p2[0, :] = (p2[0, :] + 17) % 64
        # Compare full last-token logits (argmax could coincide).
        l1, _ = jax.jit(model.forward)(e.params[MID], jnp.asarray(p1))
        l2, _ = jax.jit(model.forward)(e.params[MID], jnp.asarray(p2))
        assert not bool(jnp.all(l1[:, -1] == l2[:, -1]))
        o1 = e.dispatch(MID, (SEQ,), 2, "prefill", payload=p1).wait()
        o2 = e.dispatch(MID, (SEQ,), 2, "prefill", payload=p2).wait()
        assert bool(jnp.all(o1[:2] == l1[:, -1].argmax(-1)))
        assert bool(jnp.all(o2[:2] == l2[:, -1].argmax(-1)))

    def test_decode_prefix_payload_bit_identical(self):
        e = _engine()
        model = model_for(tiny(MID))
        toks = np.array([5, 42], np.int32)
        out = e.dispatch(MID, (SEQ_D,), 2, "decode", payload=toks).wait()
        ref, _ = jax.jit(model.decode_step)(
            e.params[MID],
            model.init_cache(2, SEQ_D),
            jnp.asarray(toks),
            jnp.full((2,), SEQ_D - 1, jnp.int32),
        )
        assert bool(jnp.all(out[:2] == ref))

    def test_decode_payload_differs_when_bytes_differ(self):
        outs = []
        for tok in (7, 9):
            e = _engine()
            outs.append(
                np.asarray(
                    e.dispatch(
                        MID, (SEQ_D,), 1, "decode",
                        payload=np.array([tok], np.int32),
                    ).wait()
                )[0]
            )
        assert not np.array_equal(outs[0], outs[1])

    def test_decode_slot_mode_dict_payload_bit_identical(self):
        e = _engine()
        model = model_for(tiny(MID))
        e.alloc_slots(MID, (SEQ_D,)[0], 3, start_pos=SEQ_D - 1)
        e.free_slots(MID, SEQ_D, [1])  # live rows 0, 2 (scattered)
        out = e.dispatch(
            MID, (SEQ_D,), 2, "decode", slots=(0, 2),
            payload={0: 11, 2: 29},
        ).wait()
        ref, _ = jax.jit(model.decode_step)(
            e.params[MID],
            model.init_cache(2, SEQ_D),
            jnp.array([11, 29], jnp.int32),
            jnp.full((2,), SEQ_D - 1, jnp.int32),
        )
        assert bool(jnp.all(out[jnp.array([0, 2])] == ref))

    def test_per_frame_row_list_cropped_to_shrunk_shape(self):
        """Adaptation's shape shrink applied to real bytes: a (SEQ,) row
        dispatched at seq SEQ//2 is cropped, matching the dense ref on
        the cropped tokens."""
        e = _engine()
        model = model_for(tiny(MID))
        row = np.arange(SEQ, dtype=np.int32) % 64
        half = SEQ // 2
        out = e.dispatch(MID, (half,), 1, "prefill", payload=[row]).wait()
        logits, _ = jax.jit(model.forward)(
            e.params[MID], jnp.asarray(row[:half][None, :])
        )
        assert bool(jnp.all(out[:1] == logits[:, -1].argmax(-1)))

    def test_payload_shape_mismatch_raises(self):
        e = _engine()
        with pytest.raises(ValueError, match="payload"):
            e.dispatch(
                MID, (SEQ,), 2, "prefill",
                payload=np.zeros((2, SEQ + 1), np.int32),
            )
        with pytest.raises(ValueError, match="slot ids"):
            e.dispatch(
                MID, (SEQ_D,), 1, "decode",
                slots=e.alloc_slots(MID, SEQ_D, 1),
                payload={99: 1},
            )

    def test_idle_leased_rows_do_not_consume_phantom_tokens(self):
        """A leased stream with no frame in a window stays INACTIVE for
        that step (step_rows): its cursor is frozen and its KV history
        never contains a phantom zero token — every stream's row matches
        a dense reference replaying only ITS OWN ingested tokens (under
        the batch-shape tolerance above), at every step, not just the
        first."""
        e = _engine(max_slots=4)
        model = model_for(tiny(MID))
        step = jax.jit(model.decode_step)
        e.alloc_slots(MID, SEQ_D, 1)  # row 0: stream A
        e.alloc_slots(MID, SEQ_D, 1)  # row 1: stream B
        live = (0, 1)
        # Window 1: only A has a frame (token 3). B idles.
        e.dispatch(
            MID, (SEQ_D,), 2, "decode", slots=live,
            payload={0: 3}, step_rows=[0],
        ).wait()
        # Window 2: both have frames (A: 5, B: 7).
        out = e.dispatch(
            MID, (SEQ_D,), 2, "decode", slots=live,
            payload={0: 5, 1: 7}, step_rows=[0, 1],
        ).wait()
        # A == dense ref replaying [3, 5].
        cache = model.init_cache(1, SEQ_D)
        _, cache = step(
            e.params[MID], cache, jnp.array([3], jnp.int32),
            jnp.zeros((1,), jnp.int32),
        )
        ref_a, _ = step(
            e.params[MID], cache, jnp.array([5], jnp.int32),
            jnp.ones((1,), jnp.int32),
        )
        assert _logits_match(out[0], ref_a[0])
        # B == dense ref of its FIRST token at cursor 0: the idle
        # window left no trace.
        ref_b, _ = step(
            e.params[MID], model.init_cache(1, SEQ_D),
            jnp.array([7], jnp.int32), jnp.zeros((1,), jnp.int32),
        )
        assert _logits_match(out[1], ref_b[0])

    def test_step_rows_must_be_live(self):
        e = _engine(max_slots=4)
        slots = e.alloc_slots(MID, SEQ_D, 2)
        with pytest.raises(ValueError, match="step_rows"):
            e.dispatch(
                MID, (SEQ_D,), 2, "decode", slots=slots, step_rows=[3]
            )

    def test_staged_sweep_zero_recompiles(self):
        """1 -> max_slots -> 1 with REAL payloads: still one program."""
        e = _engine()
        e.execute(MID, (SEQ_D,), 1, kind="decode")  # warm-up compile
        e.reset_stats()
        rng = np.random.default_rng(2)
        m = e.max_slots
        for b in list(range(1, m + 1)) + list(range(m - 1, 0, -1)):
            pay = rng.integers(0, 64, size=(b,), dtype=np.int32)
            e.dispatch(MID, (SEQ_D,), b, "decode", payload=pay)
        e.dispatch(MID, (SEQ_D,), 1, "decode").wait()
        assert e.stats["decode_compiles"] == 0
        # The staged loop allocated no fresh host buffers either.
        ring = e.staging_ring("decode", MID, SEQ_D, m)
        assert ring.host_allocs == ring.depth


class TestDoubleBufferInterleaving:
    def test_inflight_job_never_observes_next_payload(self):
        """Dispatch N, then fill+dispatch N+1 BEFORE waiting on N: both
        outputs must match their own payload's dense reference."""
        e = _engine()
        model = model_for(tiny(MID))
        fwd = jax.jit(model.forward)
        rng = np.random.default_rng(3)
        pays = [
            rng.integers(0, 64, size=(2, SEQ), dtype=np.int32)
            for _ in range(6)
        ]
        handles = []
        for i, pay in enumerate(pays):
            handles.append(e.dispatch(MID, (SEQ,), 2, "prefill", payload=pay))
            if i % 2:  # drain in pairs: two staged jobs in flight at once
                for h, p in zip(handles, pays[i - 1 : i + 1]):
                    ref = fwd(e.params[MID], jnp.asarray(p))[0][:, -1].argmax(-1)
                    assert bool(jnp.all(h.wait()[:2] == ref))
                handles = []

    @pytest.mark.slow
    def test_hypothesis_interleaved_payload_isolation(self):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis (installed in CI); a bare "
            "env skips instead of erroring at collection",
        )
        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        e = _engine()
        model = model_for(tiny(MID))
        fwd = jax.jit(model.forward)

        @settings(
            max_examples=10, deadline=None,
            suppress_health_check=[HealthCheck.too_slow],
        )
        @given(
            st.lists(
                st.lists(
                    st.integers(min_value=0, max_value=63),
                    min_size=SEQ, max_size=SEQ,
                ),
                min_size=2, max_size=4,
            )
        )
        def prop(rows):
            pays = [np.asarray([r], np.int32) for r in rows]
            # Pipeline every job before waiting on any earlier one.
            handles = [
                e.dispatch(MID, (SEQ,), 1, "prefill", payload=p) for p in pays
            ]
            for h, p in zip(handles, pays):
                ref = fwd(e.params[MID], jnp.asarray(p))[0][:, -1].argmax(-1)
                assert bool(jnp.all(h.wait()[:1] == ref))

        prop()


# ---------------------------------------------------------------------------
# Gateway over a simulated DeepRT
# ---------------------------------------------------------------------------


def _sim_table(a: float = 0.01, c: float = 0.04) -> ProfileTable:
    table = ProfileTable()
    for b in (1, 2, 4, 8, 16, 32):
        table.record("m", (4,), b, a + c * b)
    return table


CAT = Category("m", (4,))


class TestGatewaySimulation:
    def test_lifecycle_and_arrival_stamped_deadlines(self):
        sched = DeepRT(_sim_table())
        gw = IngestGateway(sched)
        src = CameraSource(
            period=0.2, n_frames=10, jitter_frac=0.4, payload_shape=(4,), seed=4
        )
        session = gw.register(src, CAT, relative_deadline=0.5)
        assert session.state == "active"
        m = sched.run()
        assert m.completed_frames == 10
        assert session.conserved()
        # Frames arrived at the SOURCE's jittered offsets (not the
        # declared period), deadline-stamped at arrival.
        offs = [f.offset for f in src.plan()]
        for i, off in enumerate(offs):
            arrival, deadline, _ = m.frame_records[(session.request_id, i)]
            assert arrival == pytest.approx(off)
            assert deadline == pytest.approx(off + 0.5)

    def test_rejected_session_delivers_nothing(self):
        # Saturate: a stream whose own declared load breaks the bound.
        sched = DeepRT(_sim_table(a=0.5, c=0.5))
        gw = IngestGateway(sched)
        src = CameraSource(period=0.1, n_frames=5, payload_shape=(4,), seed=0)
        session = gw.register(src, CAT, relative_deadline=0.2)
        assert session.state == "rejected"
        sched.run()
        assert sched.metrics.completed_frames == 0
        assert session.frames_ingested == 0

    def test_close_cancels_remaining_arrivals(self):
        sched = DeepRT(_sim_table())
        gw = IngestGateway(sched)
        src = CameraSource(period=0.2, n_frames=10, payload_shape=(4,), seed=1)
        session = gw.register(src, CAT, relative_deadline=0.5)
        sched.run(until=0.7)  # frames 0..3 arrived
        # Fired deliveries pruned themselves: only the pending tail is
        # left to cancel (cancelling fired ids would leak them into the
        # loop's cancelled-set).
        assert len(session._events) == 10 - session.frames_ingested
        gw.close(session)
        assert session._events == set()
        sched.run()
        assert session.state == "closed"
        assert sched.metrics.completed_frames < 10
        assert session.conserved()

    def test_e2e_latency_recorded(self):
        sched = DeepRT(_sim_table())
        gw = IngestGateway(sched)
        src = CameraSource(period=0.2, n_frames=6, payload_shape=(4,), seed=2)
        gw.register(src, CAT, relative_deadline=0.5)
        m = sched.run()
        assert len(m.e2e_latencies) == m.completed_frames == 6
        assert m.mean_e2e_latency > 0
        # No upstream queueing here: e2e == scheduler-arrival latency.
        assert m.e2e_latencies == pytest.approx(m.frame_latencies)


class TestLoadShedding:
    def _overloaded(self, shedding: bool, mode: str = "drop"):
        sched = DeepRT(_sim_table())
        gw = IngestGateway(
            sched,
            shedding=shedding,
            default_policy=ShedPolicy(mode=mode),
        )
        # Declared: 1 frame / 0.1s (admissible); delivered: 2.5x that in
        # bursts — the overload admission never saw.
        src = BurstSource(
            period=0.1, n_frames=50, burst=5, duty=0.4,
            payload_shape=(4,), seed=6,
        )
        session = gw.register(src, CAT, relative_deadline=0.2)
        assert session.state == "active"
        m = sched.run()
        return session, m

    def test_shedding_strictly_reduces_misses_under_overload(self):
        _, m_off = self._overloaded(shedding=False)
        s_on, m_on = self._overloaded(shedding=True)
        assert m_off.missed_frames > 0  # overload really overloads
        assert m_on.missed_frames < m_off.missed_frames
        assert m_on.dropped_frames > 0

    def test_every_dropped_frame_accounted(self):
        session, m = self._overloaded(shedding=True)
        assert session.conserved()
        assert session.frames_ingested == 50
        # delivered_frames is counted independently (at ingest_frame),
        # so this conservation check is falsifiable, not definitional.
        assert m.delivered_frames == session.frames_delivered
        assert m.completed_frames + m.dropped_frames == m.ingested_frames
        assert m.completed_frames + m.dropped_frames == 50
        assert m.drops_by_request.get(session.request_id) == m.dropped_frames

    def test_subsample_keeps_some_frames_while_over_budget(self):
        s_drop, _ = self._overloaded(shedding=True, mode="drop")
        s_sub, _ = self._overloaded(shedding=True, mode="subsample")
        assert 0 < s_sub.frames_dropped < s_drop.frames_dropped

    def test_sheds_reported_to_adaptation(self):
        sched = DeepRT(_sim_table())
        gw = IngestGateway(sched, shedding=True)
        src = BurstSource(
            period=0.1, n_frames=50, burst=5, duty=0.4,
            payload_shape=(4,), seed=6,
        )
        s = gw.register(src, CAT, relative_deadline=0.2)
        sched.run()
        assert sched.adaptation.sheds.get(CAT, 0) == s.frames_dropped > 0

    def test_penalized_category_sheds_earlier(self):
        """AdaptationModule.shed_scale tightens the budget while the
        category carries overrun penalty (the arrival-side coupling)."""
        sched = DeepRT(_sim_table())
        assert sched.adaptation.shed_scale(CAT) == 1.0
        sched.adaptation.penalties[CAT] = 0.05
        assert (
            sched.adaptation.shed_scale(CAT)
            == sched.adaptation.PENALIZED_BUDGET_TIGHTEN
            > 1.0
        )
        sched.adaptation.enabled = False
        assert sched.adaptation.shed_scale(CAT) == 1.0


class TestDisBatcherLateFrames:
    def test_frame_after_timer_retirement_still_flushes(self):
        """A jittered frame landing after the declared last arrival must
        re-arm the window timer, not strand in the queue."""
        table = _sim_table()
        sched = DeepRT(table)
        req = Request(category=CAT, period=0.1, relative_deadline=0.4, n_frames=2)
        assert sched.submit_request(req, external_arrivals=True).admitted
        sched.ingest_frame(req, 0, payload=np.zeros(4, np.int32))
        sched.run()  # drains; timer retires (requests look exhausted)
        # Late frame, well past request.end_time:
        sched.loop.schedule(
            sched.loop.now + 1.0,
            lambda: sched.ingest_frame(req, 1, payload=np.zeros(4, np.int32)),
        )
        m = sched.run()
        assert m.completed_frames == 2


# ---------------------------------------------------------------------------
# Gateway over the live cluster path (real compiled programs)
# ---------------------------------------------------------------------------


class TestGatewayLiveCluster:
    @pytest.fixture(scope="class")
    def served(self):
        from repro.serving.batcher_bridge import build_live_cluster

        configs = {MID: tiny(MID)}
        cats = [(MID, (SEQ,), "prefill"), (MID, (SEQ_D,), "decode")]
        cluster, slices = build_live_cluster(
            configs, cats, slice_names=("s0", "s1"), batch_sizes=(1, 2),
            profile_runs=2, nonrt_cap=1,
        )
        # Record every dispatched decode handle + its job so payload
        # routing can be checked against the model reference.
        captured = []
        for sl in slices.values():
            inner = sl.device.dispatch_fn

            def spy(job, _inner=inner, _sl=sl):
                handle = _inner(job)
                captured.append((_sl, job, handle))
                return handle

            sl.device.dispatch_fn = spy
        gw = IngestGateway(cluster)
        sessions = [
            gw.register(
                CameraSource(period=0.2, n_frames=4, payload_shape=(), seed=20 + i),
                Category(MID, (SEQ_D,)),
                relative_deadline=0.4,
            )
            for i in range(3)
        ]
        cluster.run()
        return cluster, slices, gw, sessions, captured

    def test_streams_admitted_and_served(self, served):
        cluster, _, _, sessions, _ = served
        assert [s.state for s in sessions] == ["active"] * 3
        agg = cluster.aggregate_metrics()
        assert agg["completed_frames"] + agg["dropped_frames"] == 12
        assert all(s.conserved() for s in sessions)

    def test_placement_spreads_streams(self, served):
        _, _, _, sessions, _ = served
        assert len({s.slice_name for s in sessions}) == 2

    def test_zero_decode_recompiles_and_ring_reuse(self, served):
        _, slices, _, _, _ = served
        for sl in slices.values():
            assert sl.engine.stats["decode_compiles"] == 0
            for ring in sl.engine._rings.values():
                assert ring.host_allocs == ring.depth

    def test_leases_released_when_streams_drain(self, served):
        _, slices, _, _, _ = served
        for sl in slices.values():
            assert sl.leases == {}
            for (mid, seq), arena in sl.engine._arenas.items():
                assert len(arena.free) == arena.max_slots

    def test_slot_payloads_route_to_leased_rows(self, served):
        """The FIRST decode job on each slice: every index-0 frame's
        ingested token must produce, at some arena row, logits matching
        (under the batch-shape tolerance above) a fresh single-row
        reference fed that token at cursor 0 — payloads reached their
        streams' resident rows.
        (Later jobs depend on each row's KV history: continuous
        batching steps ALL leased rows every window, so only the first
        job has a clean-slate reference.)"""
        _, slices, _, sessions, captured = served
        model = model_for(tiny(MID))
        step = jax.jit(model.decode_step)
        by_rid = {s.request_id: s for s in sessions}
        first_seen = set()
        checked = 0
        for sl, job, handle in captured:
            if job.category.shape_key != (SEQ_D,):
                continue
            if sl.spec.name in first_seen:
                continue
            first_seen.add(sl.spec.name)
            out = np.asarray(handle.wait())
            for frame in job.frames:
                if frame.payload is None or frame.request_id not in by_rid:
                    continue
                if frame.index != 0:
                    continue
                tok = int(np.asarray(frame.payload))
                ref, _ = step(
                    sl.engine.params[MID],
                    model.init_cache(1, SEQ_D),
                    jnp.array([tok], jnp.int32),
                    jnp.zeros((1,), jnp.int32),
                )
                matches = [
                    r for r in range(out.shape[0])
                    if _logits_match(out[r], ref[0])
                ]
                assert matches, (sl.spec.name, frame.request_id, tok)
                checked += 1
        assert checked >= 1


class TestSlotPayloadCollision:
    def test_same_stream_two_frames_one_window_counted_earliest_wins(self):
        """One decode step consumes one token per leased row: when a
        window batches two frames of the same stream, the earliest
        token stages (in order) and the collision is COUNTED — visible
        degradation, never a silent overwrite."""
        from repro.serving.batcher_bridge import build_live_cluster

        configs = {MID: tiny(MID)}
        cats = [(MID, (SEQ_D,), "decode")]
        cluster, slices = build_live_cluster(
            configs, cats, slice_names=("s0",), batch_sizes=(1, 2),
            profile_runs=2, nonrt_cap=1,
        )
        sl = slices["s0"]
        sched = sl.scheduler
        req = Request(
            category=Category(MID, (SEQ_D,)), period=0.2,
            relative_deadline=0.4, n_frames=2,
        )
        assert cluster.submit_request(req, external_arrivals=True)
        # Both frames delivered back-to-back, well inside one window.
        sched.ingest_frame(req, 0, payload=np.int32(7))
        sched.ingest_frame(req, 1, payload=np.int32(9))
        cluster.run()
        m = sched.metrics
        assert m.completed_frames == 2
        assert m.payload_collisions == 1
        assert m.delivered_frames == 2
        assert sl.leases == {}  # both frames counted: lease released


class TestLeaselessDecodeFrames:
    def test_closed_stream_frame_does_not_phantom_step_survivors(self):
        """A frame whose stream lost its lease (closed with the frame
        still queued in the window) must step NO arena row active —
        surviving streams' cursors stay frozen, no phantom zero token."""
        from repro.serving.batcher_bridge import build_live_cluster

        configs = {MID: tiny(MID)}
        cats = [(MID, (SEQ_D,), "decode")]
        cluster, slices = build_live_cluster(
            configs, cats, slice_names=("s0",), batch_sizes=(1, 2),
            profile_runs=2, nonrt_cap=1,
        )
        sl = slices["s0"]
        sched = sl.scheduler
        req_a = Request(category=Category(MID, (SEQ_D,)), period=0.2,
                        relative_deadline=0.4, n_frames=1)
        req_b = Request(category=Category(MID, (SEQ_D,)), period=0.2,
                        relative_deadline=0.4, n_frames=1)
        assert cluster.submit_request(req_a, external_arrivals=True)
        assert cluster.submit_request(req_b, external_arrivals=True)
        sched.ingest_frame(req_a, 0, payload=np.int32(5))
        # A closes before the window joint: its lease is gone but its
        # frame is already queued.
        sl.release(req_a.request_id)
        row_b = sl.leases[req_b.request_id][2][0]
        cluster.run()
        arena = sl.engine.arena(MID, SEQ_D)
        # B's cursor never advanced: no phantom zero token consumed.
        assert int(np.asarray(arena.cur)[row_b]) == 0
        assert sched.metrics.completed_frames == 1  # A's frame drained

    def test_payload_decode_without_leases_fails_loudly(self):
        """The single-device (prefix-mode) serving path must refuse
        payload-carrying decode jobs instead of assigning rows
        positionally per window (silent cross-stream corruption)."""
        from repro.serving.batcher_bridge import build_live_scheduler

        sched, engine, table = build_live_scheduler(
            {MID: tiny(MID)}, [(MID, (SEQ_D,), "decode")],
            batch_sizes=(1, 2),
        )
        gw = IngestGateway(sched)
        with pytest.raises(ValueError, match="cluster path"):
            gw.register(
                CameraSource(period=0.2, n_frames=2, payload_shape=(), seed=0),
                Category(MID, (SEQ_D,)), relative_deadline=0.4,
            )


class TestGatewayShedReleasesLease:
    def test_dropped_frames_still_release_lease(self):
        """A truncated (shed) stream must not pin its arena row forever:
        note_dropped advances the lease countdown."""
        from repro.serving.batcher_bridge import build_live_cluster

        configs = {MID: tiny(MID)}
        cats = [(MID, (SEQ_D,), "decode")]
        cluster, slices = build_live_cluster(
            configs, cats, slice_names=("s0",), batch_sizes=(1, 2),
            profile_runs=2, nonrt_cap=1,
        )
        gw = IngestGateway(cluster)
        session = gw.register(
            CameraSource(period=0.2, n_frames=4, payload_shape=(), seed=9),
            Category(MID, (SEQ_D,)),
            relative_deadline=0.4,
        )
        assert session.state == "active"
        sl = slices["s0"]
        # Force-shed half the stream by hand-invoking the drop path.
        sched = sl.scheduler
        gw._shed(session, sched, Category(MID, (SEQ_D,)))
        gw._shed(session, sched, Category(MID, (SEQ_D,)))
        session.frames_ingested += 2
        # Deliver only the remaining two frames (event ids are issued in
        # schedule order, so the two lowest are frames 0 and 1).
        for ev in sorted(session._events)[:2]:
            cluster.loop.cancel(ev)
            session._events.discard(ev)
        cluster.run()
        assert sl.leases == {}  # released despite only 2 completions
        assert sched.metrics.dropped_frames == 2
