"""DisBatcher: share of the window the device ran no job while nothing
was queued and frames were held in a DisBatcher window (host clock;
``aggregate_metrics`` ``device_idle_held_s``), in %.

A program that keeps no device-idle split gets the same split from its
``FrameTracer`` ring (:func:`ring_split`)."""
from typing import Dict, List, Tuple

_CLOSE_FRAME = {"edf_enqueue": "held", "edf_dispatch": "ready",
                "shed": "held", "lost": "held"}


def ring_split(win) -> Dict[str, float]:
    """Seconds of the window the device ran no job, as ``held`` and
    ``ready``, from the ring's spans: busy from a job's ``device_submit``
    to its ``device_complete``; ready while a frame waits between
    ``edf_enqueue`` and ``edf_dispatch``; held while a frame waits
    between ``ingest`` and ``edf_enqueue`` (or its shed or loss). Busy
    outranks ready, and ready outranks held. The ring has no stamp of
    the device finishing, so completion lag counts as busy here."""
    t0, t1 = win.recorder.window
    opened: Dict[Tuple, float] = {}
    spans: List[Tuple[float, float, str]] = []

    def close(key, t):
        start = opened.pop(key, None)
        if start is not None:
            spans.append((start, t, key[0]))

    for ev in win.tracer_events or ():
        if ev.stage == "device_submit":
            opened[("busy", ev.meta["job_id"])] = ev.t
        elif ev.stage == "device_complete":
            close(("busy", ev.meta["job_id"]), ev.t)
        elif ev.stage == "ingest":
            opened.setdefault(("held", ev.rid, ev.idx), ev.t)
        elif ev.stage in _CLOSE_FRAME:
            kind = _CLOSE_FRAME[ev.stage]
            close((kind, ev.rid, ev.idx), ev.t)
            if ev.stage == "edf_enqueue":
                opened.setdefault(("ready", ev.rid, ev.idx), ev.t)
    for key, start in opened.items():
        spans.append((start, t1, key[0]))
    edges = []
    for a, b, kind in spans:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            edges += [(a, kind, 1), (b, kind, -1)]
    edges.sort()
    count = {"busy": 0, "ready": 0, "held": 0}
    out = {"held": 0.0, "ready": 0.0}
    prev = t0
    for t, kind, d in edges:
        state = ("busy" if count["busy"] else "ready" if count["ready"]
                 else "held" if count["held"] else None)
        if state in out:
            out[state] += t - prev
        count[kind] += d
        prev = t
    return out


def read(win):
    if "device_idle_held_s" not in win.agg["open"]:
        return 100.0 * ring_split(win)["held"] / win.seconds
    return 100.0 * win.delta("agg", "device_idle_held_s") / win.seconds
