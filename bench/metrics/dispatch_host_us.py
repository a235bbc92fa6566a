"""EDF worker: mean host time of one dispatch in the window, from the
pick of a job through the device's submit returning (``aggregate_metrics``
``dispatch_host_s`` over ``dispatches``), in microseconds.

A program that does not report it gets the engine's part of it: from
the engine's dispatch call (the check's recorder stamps it) to the
ring's next ``device_submit`` span, which is stamped once submit has
returned."""
from bisect import bisect_left


def _engine_part(win) -> float:
    rec = win.recorder
    starts = [b.t for b in rec.prefill] + [s.t for s in rec.decode]
    subs = sorted(ev.t for ev in win.tracer_events or ()
                  if ev.stage == "device_submit")
    gaps = []
    for t in starts:
        i = bisect_left(subs, t)
        if i < len(subs):
            gaps.append(subs[i] - t)
    return 1e6 * sum(gaps) / len(gaps) if gaps else 0.0


def read(win):
    if "dispatch_host_s" not in win.agg["open"]:
        return _engine_part(win)
    n = win.delta("agg", "dispatches")
    return 1e6 * win.delta("agg", "dispatch_host_s") / n if n else 0.0
