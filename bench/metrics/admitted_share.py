"""Admission: streams admitted over streams offered, in %."""


def read(win):
    return 100.0 * win.admitted / win.offered if win.offered else 0.0
