"""CPU rehearsal of one cell at tiny widths, for the tests.

The cell is the real one from ``BENCHMARK.json`` (its mix, metrics and
limits), or the decode cell that left it, from ``DECODE_SPEC``; only the model is the program's ``tiny()`` configuration of
the same family in float32, the warm-up and window are short, and the
peaks are placeholders for the CPU (its numbers are no device numbers).
Periods and deadlines are stretched by ``CPU_SLOWDOWN``: on a CPU that
other test workers load, the cell's own rates can leave a two-second
window with every frame shed, which would rehearse nothing.
"""
from __future__ import annotations

import time
from pathlib import Path

from bench import harness

CPU_SLOWDOWN = 4.0
CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}
SEED = 2**33 + 101  # above 32 bits, as run seeds can be
BENCHMARK = harness.ROOT / "BENCHMARK.json"
# phi4-decode-streams left the benchmark (its on-time frames did not
# repeat, PERF.md); its files stay, and this spec keeps its path tested.
DECODE_SPEC = Path(__file__).resolve().parent / "data" / "decode_cell.json"


def rehearse(name: str, trace: bool, fault=None, seconds: float = 2.0,
             spec: Path = BENCHMARK):
    from repro.configs.registry import tiny

    t0 = time.perf_counter()
    cell = harness.load_cell(name, spec)
    streams = [dict(g, period_s=g["period_s"] * CPU_SLOWDOWN,
                    deadline_s=g["deadline_s"] * CPU_SLOWDOWN)
               for g in cell.mix["streams"]]
    cell.mix = dict(cell.mix, warmup_s=1.0, streams=streams)
    return harness.run(cell, SEED, seconds, trace, t0,
                       program_cfg=tiny(cell.config["program_arch"]),
                       peaks=CPU_PEAKS, fault=fault)


def declared(name: str, trace: bool, spec: Path = BENCHMARK):
    cell = harness.load_cell(name, spec)
    return [m["name"] for m in (cell.per_layer if trace else cell.end_to_end)]
