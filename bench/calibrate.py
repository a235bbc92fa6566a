#!/usr/bin/env python3
"""Calibration on the chip, in one process per call (one build, many
windows). Not run by the benchmark's own runs.

Knee sweep: one window per value of one stream group's count (or
period, with the deadline kept at the same multiple of it):

    python3 bench/calibrate.py sweep --workload <cell> --param count \\
        --values 16,24,32 --seconds 8 --seed 7

Readings for the limits: per seed, the widest reference-logit gap of
the served tokens (the lower reading) and, with ``--control``, of the
float8 control at the same positions (the upper reading):

    python3 bench/calibrate.py seeds --workload <cell> --seeds 1,2,3 \\
        --seconds 8 --control

Each window prints one JSON line on standard output.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def summary(win, setup_s=None):
    from bench import harness

    frames = win.frames
    e2e = harness.end_to_end(win, setup_s or 0.0)
    count = {s: sum(f["status"] == s for f in frames)
             for s in ("completed", "shed", "rejected", "unanswered", "lost")}
    late = sum(1 for f in frames if f["completion"] is not None
               and f["completion"] > f["deadline"])
    stats = {k: win.delta("stats", k) for k in ("dispatches", "real_rows")}
    return {"offered": win.offered, "admitted": win.admitted,
            "attempted": len(frames), "late": late, **count, **e2e, **stats}


def record(stack, cell, args) -> None:
    """A short traced window, kept in compact form for the reducer's test."""
    import gzip
    import shutil
    import tempfile

    import jax

    from bench import harness
    from bench import trace as TR

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(log_dir)
    try:
        win = harness.serve(stack, cell.mix, args.seed, args.seconds, True)
    finally:
        jax.profiler.stop_trace()
    events = TR.load(log_dir, win.platform)
    shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = TR.window_of(events)
    keep = {k: [e for e in events[k] if e[1] + e[2] > lo and e[1] < hi]
            for k in ("ops", "modules", "host")}
    keep["platform"] = events["platform"]
    print(json.dumps({"trace_lines": events["lines"]}), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(keep, f)
    red = TR.reduce(keep, (lo, hi))
    print(json.dumps({"ops": len(keep["ops"]), "busy_s": red["busy_ns"] / 1e9,
                      "window_s": red["window_ns"] / 1e9,
                      "programs": red["programs"], "idle_gaps": red["idle_gaps"],
                      "device_ops": red["device_ops"]}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("sweep", "seeds", "record"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--spec", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="file that names the cell (bench/tests/data/"
                         "decode_cell.json for the decode cell)")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--param", choices=("count", "period"), default="count")
    ap.add_argument("--group", type=int, default=0,
                    help="which stream group of the mix the sweep varies")
    ap.add_argument("--values", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default="trace_sample.json.gz")
    args = ap.parse_args()

    import jax

    from bench import check as C
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, Path(args.spec))
    stack = harness.build(cell, trace=args.mode == "record")
    try:
        if args.mode == "record":
            record(stack, cell, args)
        elif args.mode == "sweep":
            for v in args.values.split(","):
                mix = copy.deepcopy(cell.mix)
                g = mix["streams"][args.group]
                if args.param == "count":
                    g["count"] = int(v)
                else:
                    ratio = g["deadline_s"] / g["period_s"]
                    g["period_s"] = float(v)
                    g["deadline_s"] = ratio * float(v)
                win = harness.serve(stack, mix, args.seed, args.seconds, False)
                out = {args.param: float(v), **summary(win)}
                print(json.dumps(out), flush=True)
                win = None  # the next window's weights need the room
        else:
            for seed in [int(s) for s in args.seeds.split(",")]:
                win = harness.serve(stack, cell.mix, seed, args.seconds, False)
                w = win.extra["weights"]
                t0 = time.perf_counter()
                out = {"seed": seed, **summary(win),
                       "program": C.evaluate(win.recorder, stack.shape, w, seed)}
                out["reference_s"] = time.perf_counter() - t0
                if args.control:
                    out["control"] = C.evaluate(win.recorder, stack.shape, w,
                                                seed, quant=True)
                print(json.dumps(out), flush=True)
                win = w = None  # the next seed's weights need the room
    finally:
        stack.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
