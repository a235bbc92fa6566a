"""Double-buffered host->device staging rings (the ingest gateway's
byte path into the engine).

Real ingestion means every dispatched step carries payload bytes that
arrived over the wire moments earlier. The naive implementation
allocates a fresh host array per step (allocator traffic on the hot
loop) or reuses ONE buffer (a data race the instant an upload is
asynchronous or zero-copy: the next job's fill would overwrite bytes
the in-flight program is still reading). A ``StagingRing`` fixes both:

- ``depth`` host scratch buffers are allocated ONCE and cycled
  round-robin — steady-state staging performs ZERO fresh host
  allocations (``host_allocs`` stays equal to ``depth`` forever; the
  bench smoke asserts it);
- fill and flight never share a buffer: job N fills (and uploads from)
  scratch ``N % depth``, job N+1 fills scratch ``(N+1) % depth`` — with
  the default ``depth=2`` that is exactly "fill buffer B while the
  in-flight program reads A". On backends where ``device_put`` copies
  synchronously (cpu today) the rotation is belt-and-braces; on
  backends with zero-copy or deferred host reads it is the correctness
  mechanism, so the ring never assumes the copy.

The ring bounds how many staged jobs may be simultaneously in flight at
``depth - 1`` (one buffer is always the fill target), and it ENFORCES
that bound: the caller attaches each staged buffer's consumer (the
dispatched step's ``wait``), and ``stage`` waits for a scratch's
previous consumer before refilling it. Zero-copy uploads make this
load-bearing — ``jax.device_put`` of an aligned numpy array on the cpu
backend BORROWS the host memory (observed on this container's jax:
whether it copies is alignment-dependent), so "the upload copied, reuse
is fine" is never a safe assumption. With the guard, a caller that
pipelines deeper than ``depth - 1`` degrades to a bounded wait instead
of silently corrupting an in-flight job's tokens. The EDF worker's
submit-only-when-idle discipline keeps at most one job in flight per
device, so ``depth=2`` serves the hot path with the guard never
blocking; pipelined callers size ``depth`` up at engine construction.

Byte accounting: ``fills`` / ``bytes_staged`` are the ring's lifetime
host->device traffic — ``benchmarks/ingest_serving.py`` reports the
steady-state bytes/step from them.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np


def check_payload_dtype(arr: np.ndarray, dtype: np.dtype) -> None:
    """Reject payloads whose dtype would be silently mangled by the
    staging cast (e.g. raw float frame data handed to an int32 token
    ring): only same-kind casts (int -> int) are accepted, so a
    malformed payload fails at the gateway boundary, not as garbage
    tokens inside a compiled program."""
    if not np.can_cast(arr.dtype, dtype, casting="same_kind"):
        raise ValueError(
            f"payload dtype {arr.dtype} cannot safely stage as {dtype}"
        )


class StagingRing:
    """A fixed pool of host scratch buffers cycled round-robin.

    ``shape``/``dtype`` are the staged array's device shape — one ring
    per compiled program input (the engine keys rings by
    ``(kind, mid, seq, batch)``). ``device``: where staged arrays are
    committed (the consuming engine's device; None = JAX's default).
    """

    def __init__(
        self,
        shape: Sequence[int],
        dtype=np.int32,
        depth: int = 2,
        device: Optional[jax.Device] = None,
    ):
        if depth < 2:
            raise ValueError(
                f"staging ring depth must be >= 2 (fill + in-flight), got {depth}"
            )
        self.shape: Tuple[int, ...] = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.depth = depth
        self.device = device
        self._scratch = [np.zeros(self.shape, self.dtype) for _ in range(depth)]
        self._next = 0
        self._last_slot: Optional[int] = None
        # Per-scratch consumer guard: wait callables for the job that
        # last consumed each buffer (see ``attach_consumer``).
        self._consumers: list = [None] * depth
        # Lifetime counters (the reuse / traffic acceptance bars).
        self.host_allocs = depth  # never grows after construction
        self.fills = 0
        self.bytes_staged = 0
        self.consumer_waits = 0  # guard invocations before a refill

    @property
    def frame_nbytes(self) -> int:
        """Bytes uploaded per fill (one staged program input)."""
        return int(self._scratch[0].nbytes)

    @property
    def capacity(self) -> int:
        """Stages that may be in flight behind ONE consumer: depth - 1.

        A multi-step decode chunk stages one ring slot per step and
        attaches the SAME consumer (the chunk's completion) to each, so
        a k-step chunk needs ``k <= capacity`` — were k to reach depth,
        the k-th stage would wrap onto a slot whose guard is the chunk's
        own not-yet-dispatched wait and deadlock (or worse, overwrite a
        sibling step's bytes on a zero-copy backend). The engine sizes
        decode rings to ``max_chunk_depth + 1`` and validates against
        this property at dispatch.
        """
        return self.depth - 1

    def stage(self, fill_fn: Callable[[np.ndarray], None]) -> jax.Array:
        """Fill the next scratch buffer in place and upload it.

        If a consumer is attached to this scratch (a step dispatched
        ``depth`` fills ago), its ``wait`` runs FIRST — the refill never
        races a program still reading the buffer, even on zero-copy
        backends. ``fill_fn(scratch)`` must write the COMPLETE buffer
        contents it cares about (the scratch still holds the bytes from
        ``depth`` fills ago — the ring never zeroes for you, because
        blanket zeroing would hide partial-fill bugs AND cost a full
        extra pass per step). Returns the device array the compiled
        step consumes.
        """
        slot = self._next
        self._next = (slot + 1) % self.depth
        guard = self._consumers[slot]
        if guard is not None:
            self._consumers[slot] = None
            self.consumer_waits += 1
            guard()
        buf = self._scratch[slot]
        fill_fn(buf)
        self.fills += 1
        self.bytes_staged += buf.nbytes
        self._last_slot = slot
        return jax.device_put(buf, self.device)

    def attach_consumer(self, wait_fn: Callable[[], object]) -> None:
        """Register the consumer of the MOST RECENTLY staged buffer.

        ``wait_fn`` must block until the consuming step has finished
        reading the staged input (the engine passes the dispatched
        ``StepHandle.wait``, which blocks on the step's outputs — by
        then the inputs are consumed). The guard runs at most once, on
        the fill that wants the scratch back.
        """
        if self._last_slot is None:
            raise RuntimeError("attach_consumer before any stage()")
        self._consumers[self._last_slot] = wait_fn

    def stage_rows(
        self, rows: Optional[np.ndarray], n_rows: int
    ) -> jax.Array:
        """Stage ``rows`` into the leading ``n_rows`` slots, zero the rest.

        ``rows=None`` stages an all-zero buffer (the profiler's payload —
        WCET is payload-independent; this is the ONE staging path, not a
        synthetic side branch). Raises on shape/dtype mismatches so a
        malformed payload fails at the gateway boundary, not as silent
        garbage tokens inside a compiled program.
        """
        if n_rows < 0 or n_rows > self.shape[0]:
            raise ValueError(
                f"n_rows {n_rows} outside staged batch axis {self.shape[0]}"
            )
        arr: Optional[np.ndarray] = None
        if rows is not None:
            arr = np.asarray(rows)
            if arr.shape != (n_rows,) + self.shape[1:]:
                raise ValueError(
                    f"payload shape {arr.shape} != expected "
                    f"{(n_rows,) + self.shape[1:]} for ring {self.shape}"
                )
            check_payload_dtype(arr, self.dtype)

        def fill(buf: np.ndarray) -> None:
            if arr is None:
                buf[:] = 0
                return
            buf[:n_rows] = arr.astype(self.dtype, copy=False)
            buf[n_rows:] = 0

        return self.stage(fill)
