"""Device contract: mean dispatch-to-completion time of a completed
frame's job, on the host clock."""
from bench.stages import mean_ms


def read(win):
    return mean_ms(win, "device")
