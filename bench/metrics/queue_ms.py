"""EDF worker: mean time a completed frame's job waits to be dispatched."""
from bench.stages import mean_ms


def read(win):
    return mean_ms(win, "queue")
