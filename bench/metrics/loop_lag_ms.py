"""Serving loop: mean lateness of a loop callback in the window, the
time it ran minus the time it was scheduled for (``aggregate_metrics``
``loop_late_s`` over ``loop_callbacks``), in ms. A program that does
not report it gets the lateness of the timed callbacks that send the
window's frames (the clients' ``lateness``)."""


def read(win):
    if "loop_late_s" not in win.agg["open"]:
        late = win.extra["lateness"]
        return 1e3 * sum(late) / len(late) if late else 0.0
    n = win.delta("agg", "loop_callbacks")
    return 1e3 * win.delta("agg", "loop_late_s") / n if n else 0.0
