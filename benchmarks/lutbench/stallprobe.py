"""A host stall probe that shares nothing with the program: no JAX, no
threads, its own process.

    python3 benchmarks/lutbench/stallprobe.py --seconds 60 > stalls.jsonl

It sleeps ``--tick-ms`` at a time and times its own wake-ups on the
monotonic clock, which every process on the host shares.  Each wake-up
later than ``--over-ms`` past its due time prints one JSON line with
the monotonic time it was due and how late it came; the last line sums
the run, with the host's CPU steal time and the kernel's memory
compaction stalls over it (from ``/proc``, where readable), also when
SIGTERM ends the probe early.  Run beside a stream cell, a stall that
both see at the same monotonic time froze the whole host, not one
process.
"""
import argparse
import json
import os
import signal
import time


def proc_counters() -> dict:
    """CPU steal ticks and compaction stalls, where ``/proc`` has them."""
    out = {}
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out["steal_ticks"] = int(cpu[8])
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open("/proc/vmstat") as f:
            for line in f:
                k, v = line.split()
                if k in ("compact_stall", "pgmajfault"):
                    out[k] = int(v)
    except (OSError, ValueError):
        pass
    return out


def _stop(*_):
    raise KeyboardInterrupt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tick-ms", type=float, default=1.0)
    ap.add_argument("--over-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    tick, over = args.tick_ms * 1e-3, args.over_ms * 1e-3
    signal.signal(signal.SIGTERM, _stop)
    before = proc_counters()
    t0 = time.monotonic()
    end, due, ticks, stalls, worst = t0 + args.seconds, t0 + tick, 0, 0, 0.0
    try:
        while due < end:
            time.sleep(max(0.0, due - time.monotonic()))
            late = time.monotonic() - due
            ticks += 1
            worst = max(worst, late)
            if late > over:
                stalls += 1
                print(json.dumps({"due": due, "late_ms": late * 1e3}),
                      flush=True)
            due = max(due + tick, time.monotonic())
    except KeyboardInterrupt:
        pass
    after = proc_counters()
    print(json.dumps({
        "pid": os.getpid(), "start": t0, "seconds": args.seconds,
        "ticks": ticks, "stalls": stalls, "worst_ms": worst * 1e3,
        "counters": {k: after[k] - before[k] for k in after
                     if k in before}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
