"""Prefill program: mean least time of the window's prefill executions
(the roofline: FLOPs of the rows the program ran, or the bytes it must
move, whichever bounds it) over their mean device time in the trace, in %.

Read only in a cell whose window runs no other served program: the
trace names both of the program's steps ``jit_run``."""
from bench import model as M
from bench.trace import program_time


def read(win):
    batches = win.recorder.prefill
    runs, ns = program_time(win.trace or {"programs": {}}, win.platform)
    if not batches or runs == 0 or win.recorder.decode:
        return None
    least = sum(M.least_seconds(M.prefill_flops(win.shape, b.bucket, b.seq),
                                M.prefill_bytes(win.shape, b.bucket, b.seq),
                                win.peak) for b in batches) / len(batches)
    return 100.0 * least / (ns / 1e9 / runs)
