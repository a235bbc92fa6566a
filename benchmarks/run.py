"""Benchmark harness: one module per paper table/figure (+ roofline).

Each module's main() writes a CSV under benchmarks/results/ and returns
headline ``name,metric,value`` lines, printed here. Run:

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig7]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("fig2_table1_interference", "benchmarks.interference"),
    ("fig2cd_batching_real", "benchmarks.batching_curves"),
    ("fig4_fig5_miss_rates", "benchmarks.miss_rates"),
    ("fig6_memory", "benchmarks.memory_usage"),
    ("fig7_throughput_vs_sedf", "benchmarks.throughput_vs_sedf"),
    ("fig8_imitator_accuracy", "benchmarks.imitator_accuracy"),
    ("fig9_admission_runtime", "benchmarks.admission_runtime"),
    ("fig10_adaptation", "benchmarks.adaptation"),
    ("roofline_table", "benchmarks.roofline_report"),
    ("serving_hotpath", "benchmarks.serving_hotpath"),
    ("cluster_serving", "benchmarks.cluster_serving"),
    ("ingest_serving", "benchmarks.ingest_serving"),
    ("fault_tolerance", "benchmarks.fault_tolerance"),
    ("transport_robustness", "benchmarks.transport_robustness"),
    ("transport_churn", "benchmarks.transport_churn"),
    ("decode_chunking", "benchmarks.decode_chunking"),
    ("telemetry_overhead", "benchmarks.telemetry_overhead"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite filters")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None

    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    failures = 0
    for name, module in SUITES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(module)
            lines = mod.main()
            for line in lines:
                print(line)
            print(f"# {name}: done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name}: FAILED\n{traceback.format_exc()}", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark suite(s) failed")


if __name__ == "__main__":
    main()
