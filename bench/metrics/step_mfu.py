"""Model step: model FLOPs of the real rows served in the traced window
over the window's length times the chip's bf16 peak, in %.

Prefill rows count a causal prompt with logits at its last position;
decode rows count one token against its live positions (``bench/model.py``)."""
from bench import model as M


def read(win):
    t = win.trace
    if not t or t["window_ns"] <= 0:
        return None
    rec = win.recorder
    flops = sum(M.prefill_flops(win.shape, b.batch, b.seq) for b in rec.prefill)
    flops += sum(M.decode_step_flops(win.shape, [pos + 1 for _r, _l, pos in s.rows])
                 for s in rec.decode)
    if flops <= 0:
        return None
    return 100.0 * flops / (t["window_ns"] / 1e9 * win.peak["bf16_flops"])
