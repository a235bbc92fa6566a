"""Per-frame stage times from the program's ``FrameTracer`` spans.

The same chain the program's deadline-miss attribution folds
(``repro.core.telemetry.FrameTracer._breakdown``), read here for every
completed frame and not only the late ones: ``window`` is delivery to
the DisBatcher's window close, ``queue`` the wait in the EDF queue up
to dispatch, ``device`` dispatch to completion on the host clock.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

STAMPS = {"reassembly_deliver": "deliver", "ingest": "ingest",
          "window_close": "window_close", "edf_dispatch": "dispatch"}
DONE = ("completed", "late")


def frame_stages(win) -> List[Dict[str, float]]:
    """Stage seconds of each frame due in the window that completed."""
    due = {(f["rid"], f["idx"]) for f in win.frames
           if f["completion"] is not None}
    stamps: Dict[Tuple[int, int], Dict[str, float]] = {}
    out = []
    for ev in win.tracer_events or ():
        key = (ev.rid, ev.idx)
        if key not in due:
            continue
        slot = STAMPS.get(ev.stage)
        if slot == "dispatch":
            stamps.setdefault(key, {})[slot] = ev.t  # a retry re-stamps
        elif slot is not None:
            stamps.setdefault(key, {}).setdefault(slot, ev.t)
        elif ev.stage in DONE:
            s = stamps.pop(key, {})
            deliver = s.get("deliver", s.get("ingest"))
            if deliver is None or "window_close" not in s or "dispatch" not in s:
                continue
            out.append({
                "window": s["window_close"] - deliver,
                "queue": s["dispatch"] - s["window_close"],
                "device": ev.t - s["dispatch"],
            })
    return out


def mean_ms(win, stage: str) -> float:
    """Mean of ``stage`` over the window's completed frames; 0 where none
    completed (the run is then not correct)."""
    rows = frame_stages(win)
    if not rows:
        return 0.0
    return 1e3 * sum(r[stage] for r in rows) / len(rows)
