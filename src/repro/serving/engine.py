"""Inference engine: compiled batched steps for DeepRT categories.

Two execution regimes, matching the two step kinds of the shape pool:

- PREFILL (full forward over (b, seq) tokens -> last-token logits) is
  bucketed: one XLA program per (model, seq, batch bucket), batch sizes
  padded up to the next power of two via the SHARED
  ``repro.core.bucketing.bucket`` (the same rounding the profiler grid
  and the admission WCET lookup use), so the compile count stays
  logarithmic while the table stays consistent with what actually runs.

- DECODE (one token against a KV cache) runs on a SLOT ARENA: each
  (model, seq) owns ONE resident KV arena of ``max_slots`` rows — a
  single donated buffer that lives across steps — and ONE compiled
  program that always executes all ``max_slots`` rows. The live batch
  size is carried as DATA (a per-row active bitmap + per-row cursors),
  not as a shape:

    * zero decode recompiles at runtime: any batch 1..max_slots hits the
      same program, so a DisBatcher job crossing an old bucket boundary
      can no longer land on a cold program (the lazy-compile stall that
      could blow a deadline on its own);
    * zero cache churn: there is no per-bucket cache to re-create when
      the batch size changes — rows are assigned/freed by the slot
      allocator (``alloc_slots``/``free_slots``) and recycled with an
      in-place row reset (``kvcache.cache_reset_rows``), never by
      re-allocating the arena;
    * flat per-step cost: dead rows carry ``active=0`` so the decode
      attention path (Pallas kernel block-skip, or the dense mask) does
      no KV work for them — admission's flat decode WCET
      (``ProfileTable.record_flat``) is the cost of the program that
      really runs, at every batch size.

Hot-path design (the zero-stall serving pipeline):

- ``dispatch`` launches a step WITHOUT blocking: JAX async dispatch
  returns futures, the host thread goes straight back to scheduling, and
  the ``AsyncDevice`` waiter observes completion via ``StepHandle.wait``.
  ``execute`` (= dispatch + wait) remains the synchronous path for the
  offline profiler and the benchmarks.
- KV arenas are DONATED (``jax.jit(..., donate_argnums=...)``) where the
  backend profits from it: each decode step updates the arena in place
  (buffer identity is preserved across steps), so per-step allocation is
  O(batch) instead of O(cache). ``donate_cache=None`` resolves by
  backend: True on tpu/gpu, False on cpu — CPU XLA honors the aliasing
  but charges a fixed per-dispatch donation bookkeeping cost (~50µs+ per
  step, growing with the number of donated leaves) that swamps the
  avoided copy at small model sizes; see BENCH_serving_hotpath.json.
- Inputs are REAL ingested bytes, staged through double-buffered
  host->device rings (``repro.ingest.staging.StagingRing``, one ring
  per compiled program input, keyed (kind, mid, seq, batch)): the ring
  cycles a fixed pool of host scratch buffers — fill buffer B while the
  in-flight program reads A — so steady-state staging performs ZERO
  fresh host allocations and job N's output can never observe job
  N+1's payload. ``dispatch(payload=...)`` carries the frames' token
  bytes; ``payload=None`` stages a zero frame through the SAME ring
  (the offline profiler's input — WCET is payload-independent). The
  old preallocated synthetic-zeros buffer (`_stage`) is gone.

``max_slots`` sizing: use ``repro.core.bucketing.arena_slots`` over the
largest batch admission can produce — Phase 1 bounds the mean frames per
DisBatcher window at ``n_g = floor(sum_m W_g / p_m)``, so
``arena_slots(n_g_max + 1)`` rows suffice for every admissible job (the
ROADMAP "device contract" note records the rule). Decode dispatches
larger than ``max_slots`` are rejected loudly rather than re-shaped.
"""
from __future__ import annotations

import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import telemetry as T
from repro.core.bucketing import bucket
from repro.ingest.staging import StagingRing, check_payload_dtype
from repro.models import model_for
from repro.models.kvcache import cache_nbytes, cache_reset_rows


# Every XLA compile in the process (a persistent-cache load included):
# one ``jax.monitoring`` listener, registered with the first engine,
# counts each into the ``xla_compiles`` / ``xla_compile_s`` stats of
# every engine not frozen — the arena's row release and eager ops too,
# which the per-program counters never see.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_ENGINES: "weakref.WeakSet[InferenceEngine]" = weakref.WeakSet()
_listening = False


def _count_compile(event: str, secs: float, **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    for engine in list(_ENGINES):
        if engine.frozen:
            continue  # a failed slice's counters stay as it left them
        engine.stats["xla_compiles"] += 1
        engine.stats["xla_compile_s"] += secs


def _watch_compiles(engine: "InferenceEngine") -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        _listening = True
    _ENGINES.add(engine)


def prefill_program(model):
    """The jitted prefill step: (params, tokens (B, S)) -> next tokens.
    It computes only the rows the next token reads
    (``Transformer.next_token_logits``). The function's name is the
    program's name in a device trace (``jit_run_prefill``)."""

    def run_prefill(params, tokens):
        return model.next_token_logits(params, tokens).argmax(-1)

    return jax.jit(run_prefill)


def decode_program(model, seq: int, donate: bool):
    """The jitted slot-arena decode step for arenas of length ``seq``:
    (params, cache, tok, cur, active) -> (logits, new cache, new cur).
    ``donate`` donates the cache, so the arena updates in place."""

    def run_decode(params, cache, tok, cur, active):
        logits, new_cache = model.decode_step(
            params, cache, tok, cur, active=active
        )
        new_cur = jnp.where(active, jnp.minimum(cur + 1, seq - 1), cur)
        return logits, new_cache, new_cur

    return jax.jit(run_decode, donate_argnums=(1,) if donate else ())


@dataclass
class StepHandle:
    """One in-flight dispatched step (outputs may still be computing)."""

    outputs: Any  # jax array(s): prefill -> next tokens; decode -> logits
    mid: str
    kind: str
    true_batch: int
    bucket_batch: int  # prefill: the pow2 bucket; decode: max_slots
    steps: int = 1  # decode steps this dispatch executed (chunk depth)

    def wait(self) -> Any:
        """Block until the device finishes; returns the ready outputs."""
        jax.block_until_ready(self.outputs)
        return self.outputs


@dataclass
class SlotArena:
    """One model's resident decode state for one seq length.

    ``cache`` is the single KV buffer (batch axis = max_slots) that
    lives across steps — donated (in-place, tpu/gpu default) or
    functionally replaced (cpu default; see the donate gate in the
    module docstring). ``cur``/``active`` are DEVICE-resident
    per-row cursors and the live-slot bitmap: the compiled step consumes
    them directly and returns the advanced cursors, so steady-state
    slot-mode decode does ZERO host->device transfers — membership
    changes (alloc/free) are the only time the bitmap is re-uploaded.
    ``free`` are the unassigned row ids; ``allocs``/``resets`` count
    allocator traffic for the churn metrics.
    """

    cache: Any
    max_slots: int
    cur: jax.Array = None
    active: jax.Array = None
    free: List[int] = field(default_factory=list)
    allocs: int = 0
    resets: int = 0

    @property
    def live(self) -> Tuple[int, ...]:
        free = set(self.free)
        return tuple(i for i in range(self.max_slots) if i not in free)


class InferenceEngine:
    def __init__(
        self,
        configs: Dict[str, ModelConfig],
        seed: int = 0,
        donate_cache: Optional[bool] = None,
        masked_decode: bool = True,
        max_slots: int = 8,
        staging_depth: int = 2,
        chunk_depth: int = 1,
        device: Optional[jax.Device] = None,
    ):
        """``donate_cache``: None resolves by backend (module docstring);
        explicit True/False force it — the benchmark A/Bs both arms.
        ``masked_decode=False`` recreates blind padding (every arena row
        does full attention work) — kept ONLY for the padding-waste A/B.
        ``max_slots``: decode arena rows per (model, seq); see the
        module docstring for the sizing rule.
        ``staging_depth``: host scratch buffers per staging ring; depth-1
        bounds concurrently in-flight staged jobs (the EDF worker keeps
        at most one in flight, so 2 = classic double buffering).
        ``chunk_depth``: deepest multi-step decode chunk this engine will
        serve (``decode_chunk``). A k-step chunk stages one DECODE ring
        slot per step behind a single consumer, so decode rings are
        sized ``max(staging_depth, chunk_depth + 1)`` — the depth must
        be fixed before a ring's first use, hence a construction-time
        parameter. 1 = chunking off (rings stay at ``staging_depth``).
        ``device``: the one device this engine runs on (default
        ``jax.devices()[0]``). Params, arenas, cursors/bitmaps and staged
        inputs are COMMITTED there (``jax.device_put(x, device)``), so
        every compiled step runs on it whichever thread dispatches —
        ``jax.default_device`` alone would not do: ``jit`` places
        uncommitted inputs on the calling thread's default device.
        """
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if chunk_depth < 1:
            raise ValueError(f"chunk_depth must be >= 1, got {chunk_depth}")
        self.configs = dict(configs)
        self.models = {mid: model_for(cfg) for mid, cfg in configs.items()}
        self.device = device if device is not None else jax.devices()[0]
        if donate_cache is None:
            donate_cache = self.device.platform != "cpu"
        self.donate_cache = donate_cache
        self.masked_decode = masked_decode
        self.max_slots = max_slots
        self.params = {}
        # Created on the engine's device (no staging through device 0),
        # then committed there.
        with jax.default_device(self.device):
            key = jax.random.PRNGKey(seed)
            for i, (mid, model) in enumerate(self.models.items()):
                self.params[mid] = self._put(
                    model.init(jax.random.fold_in(key, i))
                )
        self._compiled: Dict[Tuple, Any] = {}
        self._arenas: Dict[Tuple[str, int], SlotArena] = {}
        self.staging_depth = staging_depth
        self.max_chunk_depth = chunk_depth
        self._rings: Dict[Tuple, StagingRing] = {}
        # All-active step masks per (k, max_slots): resident, the common
        # profiler/benchmark chunk input (no per-chunk host upload).
        self._full_masks: Dict[int, jax.Array] = {}
        # Prefix-mode decode inputs per (mid, seq, live-count): tiny
        # (max_slots,) arrays, cached so the steady-state hot loop does
        # zero host->device transfers.
        self._decode_inputs: Dict[Tuple, Tuple[jax.Array, jax.Array]] = {}
        self._reset_fn = jax.jit(
            cache_reset_rows, donate_argnums=(0,) if donate_cache else ()
        )
        # Set by ``freeze`` when the slice owning this engine fails: the
        # cluster layer re-admits the slice's requests elsewhere, and
        # nothing may touch this engine's arenas again.
        self.frozen = False
        # Measured padding/compile accounting.
        # ``prefill_tail_one_row`` / ``prefill_unembed_only`` count the
        # prefill programs built under each ``prefill_cut``; they describe
        # the programs the engine holds, so ``reset_stats`` keeps them.
        self.stats: Dict[str, float] = {
            "prefill_tail_one_row": 0, "prefill_unembed_only": 0,
        }
        self.reset_stats()
        _watch_compiles(self)
        # Frame-lifecycle tracer (core/telemetry.py); None = off. With it,
        # each dispatch opens ``deeprt.engine.stage`` / ``.launch`` spans
        # tagged with ``job_id``, the id of the job being dispatched
        # (set by the live bridge before each dispatch).
        self.tracer = None
        self.job_id: Optional[int] = None

    def _put(self, x):
        """Commit ``x`` (array or pytree) to this engine's device."""
        return jax.device_put(x, self.device)

    def _row_mask(self, rows: Sequence[int]) -> jax.Array:
        """(max_slots,) bool mask of ``rows``, committed to the device."""
        mask = np.zeros((self.max_slots,), bool)
        mask[list(rows)] = True
        return self._put(mask)

    def reset_stats(self) -> None:
        """Zero the padding/dispatch/compile counters. build_live_scheduler
        calls this after the offline profiling pass so ``stats`` reflects
        only served traffic — in particular ``decode_compiles`` counts
        programs built AFTER warm-up, which the slot arena holds at 0.
        ``real_rows`` counts rows that carry a frame (a decode step's
        token-bearing ``step_rows``); ``xla_compiles``/``xla_compile_s``
        every XLA compile in the process (``_count_compile``)."""
        self.stats.update(
            real_rows=0, bucket_rows=0, real_slots=0, total_slots=0,
            dispatches=0, decode_compiles=0, prefill_compiles=0,
            chunk_steps=0, xla_compiles=0, xla_compile_s=0.0,
        )

    def freeze(self) -> None:
        """Permanently disable dispatch and slot traffic (idempotent).

        Called when the slice owning this engine fails: its in-flight
        requests re-admit onto OTHER slices' arenas, so any further
        dispatch/alloc/free here is a failover bug — raise instead of
        silently mutating a dead arena. The resident buffers are left in
        place (the cluster's fault-injection tests assert they are never
        touched again); process teardown reclaims them.
        """
        self.frozen = True

    def _check_not_frozen(self, op: str) -> None:
        if self.frozen:
            raise RuntimeError(
                f"engine is frozen (its slice failed); {op} must target a "
                f"surviving slice's engine"
            )

    # ----- compiled step factories ----------------------------------------
    def _prefill_fn(self, mid: str, seq: int, batch: int):
        key = ("prefill", mid, seq, batch)
        if key not in self._compiled:
            self.stats["prefill_compiles"] += 1
            model = self.models[mid]
            if model.prefill_cut:
                self.stats[f"prefill_{model.prefill_cut}"] += 1
            self._compiled[key] = prefill_program(model)
        return self._compiled[key]

    def _decode_fn(self, mid: str, seq: int):
        """THE decode program for (mid, seq): every live batch <=
        max_slots executes this one compile — batch size is data. The
        program also advances the live rows' cursors on-device (clamped
        at the cache edge; a real system would evict), so the slot-mode
        hot loop never round-trips cursors through the host."""
        key = ("decode", mid, seq)
        if key not in self._compiled:
            self.stats["decode_compiles"] += 1
            self._compiled[key] = decode_program(
                self.models[mid], seq, self.donate_cache
            )
        return self._compiled[key]

    def _decode_chunk_fn(self, mid: str, seq: int, k: int):
        """THE k-step chunked decode program for (mid, seq, k): a
        ``jax.lax.scan`` over the exact single-step body. Cursors and the
        active bitmap are already device-resident, so the whole chunk
        runs with no host round-trip — one dispatch amortizes the host
        overhead of k steps. ``masks[i]`` gates which rows carry a REAL
        token at step i (``active & masks[i]`` is the step's live set):
        idle leased rows are masked per step exactly like single-step
        ``step_rows``, so their cursors stay frozen across the chunk.

        Bit-identity with k sequential single-step dispatches is a
        CONTRACT (tests/test_decode_chunking.py): scan compiles the
        identical step subgraph per iteration — no cross-step fusion can
        change the math — so the chunked schedule is a pure latency
        optimization, never a numerics fork.
        """
        key = ("decode_chunk", mid, seq, k)
        if key not in self._compiled:
            self.stats["decode_compiles"] += 1
            model = self.models[mid]

            def run_decode_chunk(params, cache, toks, cur, active, masks):
                def body(carry, xs):
                    cache, cur = carry
                    tok, mask = xs
                    act = active & mask
                    logits, new_cache = model.decode_step(
                        params, cache, tok, cur, active=act
                    )
                    new_cur = jnp.where(
                        act, jnp.minimum(cur + 1, seq - 1), cur
                    )
                    return (new_cache, new_cur), logits

                (new_cache, new_cur), logits = jax.lax.scan(
                    body, (cache, cur), (toks, masks)
                )
                return logits, new_cache, new_cur

            donate = (1,) if self.donate_cache else ()
            self._compiled[key] = jax.jit(run_decode_chunk, donate_argnums=donate)
        return self._compiled[key]

    # ----- slot arena ------------------------------------------------------
    def arena(self, mid: str, seq: int) -> SlotArena:
        """The resident decode arena for (mid, seq), created on first use."""
        key = (mid, seq)
        if key not in self._arenas:
            with jax.default_device(self.device):
                cache = self.models[mid].init_cache(self.max_slots, seq)
            self._arenas[key] = SlotArena(
                cache=self._put(cache),
                max_slots=self.max_slots,
                cur=self._put(np.zeros((self.max_slots,), np.int32)),
                active=self._put(np.zeros((self.max_slots,), bool)),
                free=list(range(self.max_slots)),
            )
        return self._arenas[key]

    def alloc_slots(
        self, mid: str, seq: int, n: int, start_pos: int = 0
    ) -> Tuple[int, ...]:
        """Assign ``n`` arena rows to an admitted request.

        Recycled rows are wiped by ``cache_reset_rows`` — with donation
        (the tpu/gpu default) that is a true in-place write with no
        O(arena) copy; without donation (the cpu default) XLA produces a
        fresh arena-sized buffer, the copy cost the backend gate traded
        for lower per-dispatch overhead. Either way no per-bucket cache
        objects are created or destroyed — the churn that used to happen
        on every batch-bucket change. Raises when the arena is full;
        admission sized ``max_slots`` (and the flat WCET table charges
        inf beyond it) so a full arena means an admission bug, not a
        capacity surprise.
        """
        self._check_not_frozen("alloc_slots")
        arena = self.arena(mid, seq)
        if n < 1:
            raise ValueError(f"need >= 1 slot, got {n}")
        if n > len(arena.free):
            raise RuntimeError(
                f"arena {mid}/seq={seq} exhausted: want {n}, "
                f"free {len(arena.free)}/{arena.max_slots} — admission "
                f"must bound live batches by max_slots"
            )
        slots = tuple(sorted(arena.free)[:n])
        arena.free = [s for s in arena.free if s not in slots]
        rows = self._row_mask(slots)
        arena.cache = self._reset_fn(arena.cache, rows)
        arena.cur = jnp.where(rows, jnp.int32(start_pos), arena.cur)
        arena.active = arena.active | rows
        arena.allocs += n
        arena.resets += n
        return slots

    def free_slots(self, mid: str, seq: int, slots: Sequence[int]) -> None:
        """Return rows to the allocator (wiped lazily on next alloc)."""
        self._check_not_frozen("free_slots")
        arena = self.arena(mid, seq)
        ids = [int(s) for s in slots]
        if not ids:
            return  # freeing nothing is a no-op, not an indexing error
        bad = [s for s in ids if not 0 <= s < arena.max_slots]
        if bad:
            raise ValueError(f"slot ids out of range: {bad}")
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate slot ids in free: {sorted(ids)}")
        not_live = sorted(set(ids) - set(arena.live))
        if not_live:
            raise ValueError(f"double free / never-allocated slots {not_live}")
        arena.free.extend(ids)
        rows = self._row_mask(ids)
        arena.active = arena.active & ~rows

    def arena_nbytes(self, mid: str, seq: int) -> int:
        """Resident bytes of the (mid, seq) decode arena."""
        return cache_nbytes(self.arena(mid, seq).cache)

    # ----- double-buffered input staging ----------------------------------
    def staging_ring(self, kind: str, mid: str, seq: int, batch: int) -> StagingRing:
        """The host->device staging ring for one compiled program input
        (prefill: (bucket, seq) token rows; decode: (max_slots,) tokens).
        Created on first use, then a fixed scratch pool forever — the
        steady-state hot loop performs zero fresh host allocations
        (``host_allocs`` stays at the ring's construction depth; the
        ingest bench smoke asserts it)."""
        key = (kind, mid, seq, batch)
        ring = self._rings.get(key)
        if ring is None:
            shape = (batch, seq) if kind == "prefill" else (batch,)
            # Decode rings must hold a full chunk's per-step stages (one
            # slot per step, all behind the chunk's single consumer)
            # plus the fill target — ring depth is fixed at creation, so
            # it is sized here, before any decode dispatch.
            depth = self.staging_depth
            if kind == "decode":
                depth = max(depth, self.max_chunk_depth + 1)
            ring = StagingRing(shape, np.int32, depth=depth, device=self.device)
            self._rings[key] = ring
        return ring

    def _stage_prefill_tokens(
        self, ring: StagingRing, payload, n_rows: int
    ) -> jax.Array:
        """Stage one prefill's token rows. ``payload``: None (zero
        frame), a dense (n_rows, seq) array, or a per-frame list of
        Optional row arrays — the bridge's form, written straight into
        the ring scratch (no intermediate stack allocation on the hot
        loop). Rows longer than the running seq are CROPPED — the
        adaptation module's shape shrink applied to real bytes (the
        paper's resolution shrink at the token level) — shorter rows
        zero-pad.
        """
        if payload is None or isinstance(payload, np.ndarray):
            return ring.stage_rows(payload, n_rows)
        rows = list(payload)
        if len(rows) != n_rows:
            raise ValueError(
                f"prefill payload carries {len(rows)} rows for batch {n_rows}"
            )
        seq_run = ring.shape[1]

        arrs = []
        for r in rows:
            if r is None:
                arrs.append(None)
                continue
            arr = np.asarray(r).ravel()
            check_payload_dtype(arr, ring.dtype)
            arrs.append(arr)

        def fill(buf: np.ndarray) -> None:
            for i, arr in enumerate(arrs):
                if arr is None:
                    buf[i] = 0
                    continue
                n = min(arr.size, seq_run)
                buf[i, :n] = arr[:n]
                buf[i, n:] = 0
            buf[n_rows:] = 0

        return ring.stage(fill)

    def _stage_decode_tokens(
        self, ring: StagingRing, payload, prefix_rows: Optional[int]
    ) -> jax.Array:
        """Stage one decode step's token vector (all ``max_slots`` rows).

        ``prefix_rows`` set (prefix-mode dispatch): ``payload`` is None,
        a (prefix_rows,) token array, or a per-frame list of Optional
        scalars for the leading rows. Otherwise (slot mode): ``payload``
        is None, a full (max_slots,) slot-aligned array, or a
        {slot_id: token} dict — the bridge builds the dict from each
        frame's arena lease, so every stream's token lands in its own
        resident row.
        """
        if payload is None:
            return ring.stage_rows(None, 0)
        if prefix_rows is not None:
            if isinstance(payload, np.ndarray):
                return ring.stage_rows(payload, prefix_rows)
            toks = list(payload)
            if len(toks) != prefix_rows:
                raise ValueError(
                    f"decode payload carries {len(toks)} tokens for "
                    f"batch {prefix_rows}"
                )

            def fill_prefix(buf: np.ndarray) -> None:
                buf[:] = 0
                for i, t in enumerate(toks):
                    if t is not None:
                        buf[i] = int(np.asarray(t))

            return ring.stage(fill_prefix)
        if isinstance(payload, dict):
            m = ring.shape[0]
            bad = [s for s in payload if not 0 <= int(s) < m]
            if bad:
                raise ValueError(f"decode payload slot ids out of range: {bad}")

            def fill(buf: np.ndarray) -> None:
                buf[:] = 0
                for s, tok in payload.items():
                    buf[int(s)] = tok

            return ring.stage(fill)
        return ring.stage_rows(payload, ring.shape[0])

    def _prefix_inputs(
        self, mid: str, seq: int, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        """(cursors, active) for a job occupying the first ``k`` arena
        rows: live rows sit at position seq-1, dead rows carry active=0
        so the attention path skips ALL their KV blocks. Cached per
        (mid, seq, k) — the hot loop re-sends resident device arrays."""
        if not self.masked_decode:
            k = self.max_slots  # blind padding: every row does full work
        key = (mid, seq, k)
        if key not in self._decode_inputs:
            live = np.arange(self.max_slots) < k
            cur = np.where(live, seq - 1, 0).astype(np.int32)
            self._decode_inputs[key] = (self._put(cur), self._put(live))
        return self._decode_inputs[key]

    # ----- execution ---------------------------------------------------------
    def warmup(self, mid: str, shape_key: Tuple[int, ...], batch_sizes,
               kind: str = "prefill") -> None:
        for b in batch_sizes:
            self.execute(mid, shape_key, b, kind)

    def dispatch(
        self, mid: str, shape_key: Tuple[int, ...], batch_size: int,
        kind: str = "prefill", slots: Optional[Sequence[int]] = None,
        payload=None, step_rows: Optional[Sequence[int]] = None,
    ) -> StepHandle:
        """Launch one batched job WITHOUT waiting for the device.

        Returns immediately after JAX async dispatch; the returned
        handle's ``wait()`` blocks until the result is ready (the
        AsyncDevice calls it from the waiter thread).

        shape_key = (seq_len,) for LM categories. Decode jobs run on the
        slot arena: ``slots`` steps the allocator-assigned rows
        (continuous batching — the set must be ALL currently live rows:
        every step writes each live row's cache at its cursor, so
        stepping a strict subset would clobber the skipped rows; masked
        per-row cache writes are the extension point if partial stepping
        is ever needed); ``slots=None`` uses the first ``batch_size``
        rows (the profiler/benchmark workload). Either way the SAME
        compiled program executes — only the active bitmap and cursors
        change, and in slot mode both are device-resident; the staged
        token vector is the ONE per-step host->device transfer.

        ``payload`` carries the job's real ingested bytes through the
        staging ring: prefill takes a (batch_size, seq) int32 token
        array (rows beyond the true batch stage as zeros inside the
        bucket); decode takes a (batch_size,) array in prefix mode or a
        slot-aligned array / {slot: token} dict in slot mode. ``None``
        stages a zero frame — same ring, the profiler's input.

        ``step_rows`` (slot mode only): the subset of live rows that
        carry a REAL token this step. Rows outside it stay allocator-
        live but run with ``active=0``: their attention is masked and
        their cursor does NOT advance, so a leased stream with no frame
        in this window never consumes a phantom zero token — its
        unconditional cache write lands at the frozen cursor and is
        overwritten by the stream's next real token before anything
        attends to it. (Recurrent-state blocks — rwkv/rglru — update
        state unconditionally regardless of ``active``; idle-row
        fidelity for those is the same pre-existing caveat as prefix-
        mode dead rows.) ``None`` = every live row is active (the
        profiler / single-stream workload).
        """
        self._check_not_frozen("dispatch")
        seq = shape_key[0]
        self.stats["dispatches"] += 1
        if kind == "prefill":
            b = bucket(batch_size)
            self.stats["real_rows"] += batch_size
            self.stats["bucket_rows"] += b
            fn = self._prefill_fn(mid, seq, b)
            ring = self.staging_ring("prefill", mid, seq, b)
            with T.span(self.tracer, "deeprt.engine.stage", self.job_id):
                tokens = self._stage_prefill_tokens(ring, payload, batch_size)
            with T.span(self.tracer, "deeprt.engine.launch", self.job_id):
                out = fn(self.params[mid], tokens)
            handle = StepHandle(out, mid, kind, batch_size, b)
            # The handle's wait guards this scratch buffer's reuse: the
            # ring refills it only after this step finished reading it
            # (zero-copy uploads alias host memory — see StagingRing).
            ring.attach_consumer(handle.wait)
            return handle
        if batch_size > self.max_slots:
            raise ValueError(
                f"decode batch {batch_size} > max_slots {self.max_slots}: "
                f"size the arena via bucketing.arena_slots at engine build"
            )
        m = self.max_slots
        arena = self.arena(mid, seq)
        fn = self._decode_fn(mid, seq)
        ring = self.staging_ring("decode", mid, seq, m)
        with T.span(self.tracer, "deeprt.engine.stage", self.job_id):
            tok = self._stage_decode_tokens(
                ring, payload, prefix_rows=batch_size if slots is None else None
            )
        rows = batch_size  # rows that carry a token this step
        if slots is None:
            if len(arena.free) != arena.max_slots:
                raise ValueError(
                    f"arena {mid}/seq={seq} has allocator-live rows "
                    f"{sorted(arena.live)}; prefix-mode dispatch would "
                    f"overwrite their KV at synthetic cursors — pass "
                    f"slots= (all live rows) instead"
                )
            cur, active = self._prefix_inputs(mid, seq, batch_size)
        else:
            ids = [int(s) for s in slots]
            if len(ids) != batch_size or len(set(ids)) != len(ids):
                raise ValueError(
                    f"need {batch_size} distinct slot ids, got {ids}"
                )
            if set(ids) != set(arena.live):
                raise ValueError(
                    f"slot dispatch must step ALL live rows "
                    f"{sorted(arena.live)}, got {sorted(ids)}"
                )
            cur, active = arena.cur, arena.active
            if step_rows is not None:
                step = [int(s) for s in step_rows]
                extra = sorted(set(step) - set(ids))
                if extra:
                    raise ValueError(
                        f"step_rows {extra} are not live rows {sorted(ids)}"
                    )
                active = arena.active & self._row_mask(step)
                rows = len(set(step))
        k = batch_size if self.masked_decode else m
        self.stats["real_rows"] += rows
        self.stats["bucket_rows"] += m
        self.stats["real_slots"] += batch_size * seq
        self.stats["total_slots"] += k * seq
        with T.span(self.tracer, "deeprt.engine.launch", self.job_id):
            logits, new_cache, new_cur = fn(
                self.params[mid], arena.cache, tok, cur, active
            )
        # The arena pytree is REPLACED every step (with donation the new
        # leaves alias the old buffers — in-place; without, XLA copied).
        arena.cache = new_cache
        if slots is not None:
            arena.cur = new_cur  # advanced on-device, no host round-trip
        handle = StepHandle(logits, mid, kind, batch_size, m)
        ring.attach_consumer(handle.wait)
        return handle

    def decode_chunk(
        self, mid: str, shape_key: Tuple[int, ...], batch_size: int, k: int,
        slots: Optional[Sequence[int]] = None,
        payloads: Optional[Sequence] = None,
        step_rows: Optional[Sequence[Optional[Sequence[int]]]] = None,
    ) -> StepHandle:
        """Launch ONE k-step decode chunk without waiting for the device.

        The chunked twin of a decode ``dispatch``: the same slot-arena
        semantics (``slots`` must be ALL live rows; prefix mode when
        ``slots=None``), executed k steps deep by the scanned program
        from ``_decode_chunk_fn`` — bit-identical to k sequential
        single-step dispatches, with the k-1 intermediate host returns
        removed.

        ``payloads``: length-k sequence of per-step decode payloads
        (each in any form single-step ``dispatch`` accepts: None, a
        slot-aligned array, or a {slot: token} dict); ``None`` = all
        steps zero-staged (the profiler's input). Each step's tokens go
        through the SAME decode staging ring — one ring slot per step,
        all guarded by this chunk's completion — so ``k`` must not
        exceed ``ring.capacity`` (the engine sizes decode rings from
        ``chunk_depth`` at construction; a deeper ad-hoc chunk is
        rejected loudly rather than allowed to deadlock on its own
        not-yet-dispatched consumer).

        ``step_rows``: length-k sequence of per-step frame-bearing row
        subsets (``None`` entry = every live row steps). Idle leased
        rows at step i run masked: attention skipped, cursor frozen —
        identical to single-step ``step_rows``, held per step across
        the chunk.
        """
        self._check_not_frozen("decode_chunk")
        seq = shape_key[0]
        m = self.max_slots
        if k < 1:
            raise ValueError(f"chunk depth must be >= 1, got {k}")
        if batch_size > m:
            raise ValueError(
                f"decode batch {batch_size} > max_slots {m}: size the "
                f"arena via bucketing.arena_slots at engine build"
            )
        if payloads is not None and len(payloads) != k:
            raise ValueError(
                f"chunk of depth {k} needs {k} per-step payloads, "
                f"got {len(payloads)}"
            )
        if step_rows is not None and len(step_rows) != k:
            raise ValueError(
                f"chunk of depth {k} needs {k} per-step row sets, "
                f"got {len(step_rows)}"
            )
        arena = self.arena(mid, seq)
        ring = self.staging_ring("decode", mid, seq, m)
        if k > ring.capacity:
            raise ValueError(
                f"chunk depth {k} exceeds the decode ring's in-flight "
                f"capacity {ring.capacity}: build the engine with "
                f"chunk_depth >= {k}"
            )
        if slots is None:
            if len(arena.free) != arena.max_slots:
                raise ValueError(
                    f"arena {mid}/seq={seq} has allocator-live rows "
                    f"{sorted(arena.live)}; prefix-mode decode_chunk "
                    f"would overwrite their KV at synthetic cursors — "
                    f"pass slots= (all live rows) instead"
                )
            cur, active = self._prefix_inputs(mid, seq, batch_size)
        else:
            ids = [int(s) for s in slots]
            if len(ids) != batch_size or len(set(ids)) != len(ids):
                raise ValueError(
                    f"need {batch_size} distinct slot ids, got {ids}"
                )
            if set(ids) != set(arena.live):
                raise ValueError(
                    f"slot dispatch must step ALL live rows "
                    f"{sorted(arena.live)}, got {sorted(ids)}"
                )
            cur, active = arena.cur, arena.active
            if step_rows is not None:
                for i, rows_i in enumerate(step_rows):
                    if rows_i is None:
                        continue
                    extra = sorted(set(int(s) for s in rows_i) - set(ids))
                    if extra:
                        raise ValueError(
                            f"step {i} rows {extra} are not live rows "
                            f"{sorted(ids)}"
                        )
        # Per-step token staging: one ring slot per step, every slot
        # guarded by THIS chunk's completion (the guard closure resolves
        # the handle after dispatch; a later chunk's refill of any of
        # these scratches blocks until this chunk finished reading).
        pending: Dict[str, Optional[StepHandle]] = {"handle": None}

        def _chunk_guard() -> None:
            h = pending["handle"]
            if h is not None:
                h.wait()

        staged = []
        prefix = batch_size if slots is None else None
        with T.span(self.tracer, "deeprt.engine.stage", self.job_id):
            for i in range(k):
                payload_i = payloads[i] if payloads is not None else None
                staged.append(
                    self._stage_decode_tokens(ring, payload_i, prefix_rows=prefix)
                )
                ring.attach_consumer(_chunk_guard)
            toks = jnp.stack(staged)
            masks = self._step_masks(k, step_rows)
        fn = self._decode_chunk_fn(mid, seq, k)
        kk = batch_size if self.masked_decode else m
        # Rows that carry a token, summed over the chunk's steps.
        rows = batch_size * k
        if step_rows is not None:
            rows = sum(batch_size if r is None else len({int(s) for s in r})
                       for r in step_rows)
        self.stats["dispatches"] += 1
        self.stats["chunk_steps"] += k
        self.stats["real_rows"] += rows
        self.stats["bucket_rows"] += m * k
        self.stats["real_slots"] += batch_size * seq * k
        self.stats["total_slots"] += kk * seq * k
        with T.span(self.tracer, "deeprt.engine.launch", self.job_id):
            logits, new_cache, new_cur = fn(
                self.params[mid], arena.cache, toks, cur, active, masks
            )
        arena.cache = new_cache
        if slots is not None:
            arena.cur = new_cur
        handle = StepHandle(logits, mid, "decode", batch_size, m, steps=k)
        pending["handle"] = handle
        return handle

    def _step_masks(
        self, k: int, step_rows: Optional[Sequence[Optional[Sequence[int]]]]
    ) -> jax.Array:
        """The (k, max_slots) per-step frame mask a chunk consumes.

        All-active masks (the profiler / single-stream case) are cached
        resident per depth; real per-step subsets build one small numpy
        buffer and upload it — the chunk's only host->device transfer
        besides the staged tokens."""
        m = self.max_slots
        if step_rows is None or all(r is None for r in step_rows):
            if k not in self._full_masks:
                self._full_masks[k] = self._put(np.ones((k, m), bool))
            return self._full_masks[k]
        buf = np.zeros((k, m), bool)
        for i, rows_i in enumerate(step_rows):
            if rows_i is None:
                buf[i, :] = True
            else:
                for s in rows_i:
                    buf[i, int(s)] = True
        return self._put(buf)

    def execute(
        self, mid: str, shape_key: Tuple[int, ...], batch_size: int,
        kind: str = "prefill", slots: Optional[Sequence[int]] = None,
        payload=None,
    ) -> float:
        """Run one batched job synchronously; returns wall seconds. The
        offline profiler path (and the benchmarks' latency probes)."""
        t0 = time.perf_counter()
        self.dispatch(
            mid, shape_key, batch_size, kind, slots=slots, payload=payload
        ).wait()
        return time.perf_counter() - t0

    def execute_chunk(
        self, mid: str, shape_key: Tuple[int, ...], batch_size: int, k: int,
        slots: Optional[Sequence[int]] = None,
        payloads: Optional[Sequence] = None,
    ) -> float:
        """Run one k-step decode chunk synchronously; returns wall
        seconds. The offline profiler's per-depth measurement path (and
        the benchmarks' chunk latency probes)."""
        t0 = time.perf_counter()
        self.decode_chunk(
            mid, shape_key, batch_size, k, slots=slots, payloads=payloads
        ).wait()
        return time.perf_counter() - t0

    # ----- accounting -----------------------------------------------------
    @property
    def staging_bytes(self) -> int:
        """Lifetime host->device payload bytes staged across all rings."""
        return sum(r.bytes_staged for r in self._rings.values())

    @property
    def staging_fills(self) -> int:
        return sum(r.fills for r in self._rings.values())

    @property
    def staging_host_allocs(self) -> int:
        """Host scratch buffers ever allocated; equals
        ``staging_depth * len(rings)`` forever — the zero-per-step-
        allocation bar the ingest bench asserts."""
        return sum(r.host_allocs for r in self._rings.values())

    def job_bytes(
        self, mid: str, shape_key: Tuple[int, ...], batch_size: int,
        kind: str = "prefill", steps: int = 1,
    ) -> float:
        """Bytes a running job pins on-device (staging + the arena it
        executes against).

        The arena is model-resident (it neither grows nor moves with the
        batch), but the device runs one job at a time, so charging it to
        the in-flight decode job keeps ``resident_bytes``/``peak_bytes``
        reflecting the KV memory decode actually holds — same contract
        the per-bucket caches had.
        """
        seq = shape_key[0]
        if kind == "prefill":
            return float(4 * bucket(batch_size) * seq)  # int32 tokens
        # steps > 1: a chunk stages one token vector per step (plus the
        # (steps, max_slots) bool step-mask plane) on top of the shared
        # cursors/active pair; steps == 1 is the classic tok+cur+active.
        staging = (2 + steps) * 4 * self.max_slots
        if steps > 1:
            staging += steps * self.max_slots
        return float(staging + self.arena_nbytes(mid, seq))

    @property
    def padding_waste(self) -> float:
        """Measured fraction of attended decode KV slots spent on dead
        rows (0.0 under the masked arena: dead rows attend to nothing)."""
        if self.stats["total_slots"] == 0:
            return 0.0
        return max(0.0, 1.0 - self.stats["real_slots"] / self.stats["total_slots"])

    def telemetry(self) -> Dict[str, object]:
        """JSON-able execution-substrate snapshot: per-arena occupancy
        and allocator churn, staging-ring reuse, compile/dispatch
        counters. Registered as a cluster ``telemetry_probes`` entry by
        the live factory so ``ClusterScheduler.telemetry_snapshot`` folds
        engine state in without core importing serving."""
        arenas = {}
        for (mid, seq), arena in self._arenas.items():
            arenas[f"{mid}/seq{seq}"] = {
                "max_slots": arena.max_slots,
                "free": len(arena.free),
                "occupied": arena.max_slots - len(arena.free),
                "allocs": arena.allocs,
                "resets": arena.resets,
                "nbytes": self.arena_nbytes(mid, seq),
            }
        return {
            "arenas": arenas,
            "staging": {
                "rings": len(self._rings),
                "bytes": self.staging_bytes,
                "fills": self.staging_fills,
                "host_allocs": self.staging_host_allocs,
            },
            "stats": dict(self.stats),
            "padding_waste": self.padding_waste,
            "frozen": self.frozen,
        }
