"""DisBatcher: mean time a completed frame waits for its window to close."""
from bench.stages import mean_ms


def read(win):
    return mean_ms(win, "window")
