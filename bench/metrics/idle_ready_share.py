"""Device contract: share of the window the device ran no job while a
job was queued, or a finished job's completion was not yet handled on
the loop (host clock; ``aggregate_metrics`` ``device_idle_ready_s``),
in %. A program that keeps no device-idle split gets it from its
``FrameTracer`` ring, without the completion lag
(``idle_held_share.ring_split``)."""
from bench.metrics.idle_held_share import ring_split


def read(win):
    if "device_idle_ready_s" not in win.agg["open"]:
        return 100.0 * ring_split(win)["ready"] / win.seconds
    return 100.0 * win.delta("agg", "device_idle_ready_s") / win.seconds
