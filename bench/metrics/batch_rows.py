"""DisBatcher: rows that carry a frame per dispatch in the window: a
prefill batch's real rows, a decode step's rows that take a token."""


def read(win):
    rec = win.recorder
    n = len(rec.prefill) + len(rec.decode)
    rows = sum(b.batch for b in rec.prefill) + sum(len(s.rows) for s in rec.decode)
    return rows / n if n else 0.0
