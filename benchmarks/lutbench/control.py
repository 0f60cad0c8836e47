"""Read the comparison's two readings for a cell, on the chip: the
program's (lower reading) and the control's (upper reading).

    python3 benchmarks/lutbench/control.py --workload hdr-add2.bulk \\
        --seeds 1,2,3 --seconds 3

For each seed the cell is built as a run builds it and driven through a
short window at its own load; then the numbers the run compares are
read twice: once for the program's answers, and once with the control
in the program's place, the reference computed on tables stored one
integer width lower (``reference.lower_precision``).  One JSON line per
seed.  The benchmark's own runs never run the control.
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
from run import Tracer  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    drv = bench.driver(cell.traffic["kind"])
    with tempfile.TemporaryDirectory(prefix="lutbench-") as workdir:
        st = drv.setup(cell, seed, seconds, workdir,
                       lambda _: contextlib.nullcontext())
    m = drv.window(st, seconds, Tracer(None))
    drv.close(st)
    return {"seed": seed, "attempted": m.attempted,
            "program": {k: v for k, (v, _) in drv.check(st, m).items()},
            "control": {k: v for k, (v, _) in
                        drv.check(st, m, lower_precision=True).items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax

    cell = bench.load_cell(bench.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    from repro.xla_env import use_compile_cache

    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds)
        r.update(workload=args.workload,
                 device_kind=jax.devices()[0].device_kind)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
