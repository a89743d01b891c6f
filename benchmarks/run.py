"""Benchmark harness: one module per paper table/figure + the roofline
analysis.  Prints ``name,us_per_call,derived`` CSV rows per experiment.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

NOTE: the roofline module reads the dry-run artifacts under
benchmarks/results/dryrun (produced by ``python -m repro.launch.dryrun
--all``); it does not recompile anything here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (engine_bench, ensemble_bench, faults_bench,
                            fig3_workflow_profiles, fig45_runtimes,
                            fig67_usage, fig8_multiworkflow, kernel_bench,
                            perf_variants, prediction_bench, realexec_bench,
                            recovery_bench, roofline, sizing_bench,
                            table4_profiling, tenancy_bench)
    suites = {
        "table4": table4_profiling.main,
        "fig3": fig3_workflow_profiles.main,
        "fig45": fig45_runtimes.main,
        "fig67": fig67_usage.main,
        "fig8": fig8_multiworkflow.main,
        "tenancy": tenancy_bench.main,
        "sizing": sizing_bench.main,
        "prediction": prediction_bench.main,
        "faults": faults_bench.main,
        "roofline": roofline.main,
        "perf": perf_variants.main,
        "kernels": kernel_bench.main,
        "engine": engine_bench.main,
        "ensemble": ensemble_bench.main,
        "realexec": realexec_bench.main,
        "recovery": recovery_bench.main,
    }
    os.makedirs(RESULTS, exist_ok=True)
    all_out = {}
    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            out = fn(quick=args.quick)
            all_out[name] = out
            print(f"# suite {name} done in {time.time()-t0:.1f}s\n")
        except Exception as e:  # pragma: no cover
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}\n")
            all_out[name] = {"error": str(e)}
            failed.append(name)

    def _clean(o):
        if isinstance(o, dict):
            return {str(k): _clean(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [_clean(v) for v in o]
        if hasattr(o, "item"):
            return o.item()
        return o

    with open(os.path.join(RESULTS, "bench_summary.json"), "w") as f:
        json.dump(_clean(all_out), f, indent=1)
    print("# wrote", os.path.join(RESULTS, "bench_summary.json"))
    if failed:
        sys.exit(f"# suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
