"""Sweep the offered rate of a stream cell once, on the chip, to find
its knee: the highest rate at which completions keep up with arrivals
and the p95 latency stays within the traffic file's ``p95_limit_ms``.

    python3 benchmarks/lutbench/sweep.py --workload jsc-xl-add2.stream \\
        --rates 10000,20000,30000 --seconds 5 --seed 1

One process builds the cell once and runs one window per rate.  Each
rate prints one JSON line: the offered and completed rates, the share
of requests answered within the limit, p50, p95 and p99 latency, the
generator's p99 lateness, the mean batch fill and the comparison with
the reference.  The cell's traffic file takes ``0.8 x knee`` as its
rate, written in by hand.
"""
import argparse
import contextlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

import bench  # noqa: E402
import tables  # noqa: E402
from measure import percentile  # noqa: E402
from run import GcPauses, Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    cell = bench.load_cell(bench.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    from repro.xla_env import use_compile_cache

    use_compile_cache()
    drv = bench.driver("stream")
    limit_s = cell.traffic["p95_limit_ms"] * 1e-3
    with tempfile.TemporaryDirectory(prefix="lutbench-") as workdir:
        st = drv.setup(cell, args.seed, args.seconds, workdir,
                       lambda _: contextlib.nullcontext())
    rng = tables.host_rng(args.seed, 9)
    for rate in (float(r) for r in args.rates.split(",")):
        st.offsets, st.row_of = drv.schedule(
            rate, args.seconds, cell.traffic["row_pool"], rng)
        drv.plan(st)
        pauses = GcPauses()
        m = drv.window(st, args.seconds, Tracer(None))
        pauses.close()
        done_in_window = sum(
            1 for k, h in enumerate(st.handles)
            if h is not None and h.done and not h.failed
            and h.t_done <= st.t0 + st.offsets[-1])
        checks = drv.check(st, m)
        print(json.dumps({
            "workload": args.workload, "offered_per_s": rate,
            "completed_per_s": done_in_window / m.window_s,
            "within_limit_share": float((m.latency_s <= limit_s).mean()),
            "p50_ms": percentile(m.latency_s, 50) * 1e3,
            "p95_ms": percentile(m.latency_s, 95) * 1e3,
            "p99_ms": percentile(m.latency_s, 99) * 1e3,
            "gen_late_p99_ms": percentile(m.lateness_s, 99) * 1e3,
            "mean_fill": (sum(f.fill for f in m.flushes)
                          / max(1, len(m.flushes))),
            "requests": m.attempted, "failed": m.failed,
            "mismatched_rows": checks["mismatched_rows"][0],
            "gc_collections": pauses.counts,
            "gc_longest_ms": pauses.max_s * 1e3,
            "device_kind": jax.devices()[0].device_kind}), flush=True)
    drv.close(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())
