"""Gateway: frames shed over frames ingested in the window, in %."""


def read(win):
    ingested = win.delta("agg", "ingested_frames")
    if ingested <= 0:
        return 0.0
    return 100.0 * win.delta("agg", "dropped_frames") / ingested
