"""Decode program: mean least time of the window's decode steps (the
weights, and the K/V of each active row's live positions, read once;
one position per active row written) over their mean device time in
the trace, in %.

Read only in a cell whose window runs no other served program: the
trace names both of the program's steps ``jit_run``."""
from bench import model as M
from bench.trace import program_time


def read(win):
    steps = win.recorder.decode
    runs, ns = program_time(win.trace or {"programs": {}}, win.platform)
    if not steps or runs == 0 or win.recorder.prefill:
        return None
    least = 0.0
    for s in steps:
        ctx = [pos + 1 for _r, _l, pos in s.rows]
        least += M.least_seconds(M.decode_step_flops(win.shape, ctx),
                                 M.decode_step_bytes(win.shape, ctx), win.peak)
    return 100.0 * (least / len(steps)) / (ns / 1e9 / runs)
