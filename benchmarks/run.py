"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full pass
    PYTHONPATH=src python -m benchmarks.run --fast     # reduced steps
    PYTHONPATH=src python -m benchmarks.run --only table2,roofline

Every module prints its own CSV table; the runner adds a wall-time
summary row per module (name,seconds,status).
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

# virtual host devices for the sharded lut_infer series — must be set
# before ANY benchmark module initialises jax
from repro.xla_env import ensure_host_devices, use_compile_cache

ensure_host_devices(4)

MODULES = [
    ("table2", "benchmarks.table2_polylut_add"),
    ("fig7", "benchmarks.fig7_deeper_wider"),
    ("table4", "benchmarks.table4_iso_accuracy"),
    ("fig8", "benchmarks.fig8_heatmap"),
    ("fig9", "benchmarks.fig9_sparsity_modes"),
    ("table7", "benchmarks.table7_connectivity"),
    ("table8", "benchmarks.table8_cost_model"),
    ("table9", "benchmarks.table9_runtime"),
    ("kernels", "benchmarks.kernels_bench"),
    ("lut_infer", "benchmarks.lut_infer_bench"),
    ("roofline", "benchmarks.roofline_report"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced step counts (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of module names")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_lut_infer.json at the repo root "
                         "(stable schema, tracked across PRs)")
    args = ap.parse_args()
    use_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    summary = []
    for name, modpath in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modpath, fromlist=["run", "main"])
            if hasattr(mod, "run"):
                kw = {"fast": args.fast}
                # thread --json to any benchmark whose run() takes it
                if "write_json" in inspect.signature(mod.run).parameters:
                    kw["write_json"] = args.json
                mod.run(**kw)
            else:
                mod.main()
            status = "ok"
        except Exception:
            traceback.print_exc()
            status = "FAILED"
        summary.append((name, round(time.time() - t0, 1), status))

    print("\n== benchmark summary ==")
    print("module,seconds,status")
    for row in summary:
        print(",".join(str(x) for x in row))
    if any(s[-1] != "ok" for s in summary):
        sys.exit(1)


if __name__ == "__main__":
    main()
