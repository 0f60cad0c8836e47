"""Run one cell of the LUT serving benchmark once, on the chip.

    python3 benchmarks/lutbench/run.py --workload jsc-xl-add2.stream \\
        --seed 1 --seconds 20 --trace 0

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name from ``BENCHMARK.json`` (see ``bench.py``).  The run
builds the cell (seeded random tables saved as an artifact and
cold-loaded, compiled and warmed), measures for ``--seconds``, checks
every compared answer against the plain reference, and prints:

* the parts of the set-up, and each number compared beside its limit,
  on standard error (the comparisons last);
* as the last line of standard output, one JSON object: ``correct``,
  ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
  metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
  with ``--trace 1`` ``breakdown``, and ``checks`` last.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is
kept inside the checkout (``repro.xla_env.use_compile_cache``), so only
a checkout's first run of a cell compiles.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import bench  # noqa: E402
import measure  # noqa: E402

TRACE_DIR = os.path.join(HERE, "out", "trace")
TRACE_SECONDS = 3.0


class Tracer:
    """Starts and stops the profiler around a window, when asked to."""

    def __init__(self, log_dir):
        self.log_dir = log_dir

    def start(self) -> None:
        if self.log_dir is None:
            return
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no per-call Python events
        opts.host_tracer_level = 1         # annotations, not runtime detail
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        if self.log_dir is not None:
            import jax

            jax.profiler.stop_trace()


class CompileCount:
    """Counts XLA compile requests (and persistent-cache hits)."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_listener(on_event)


class GcPauses:
    """Collections and their pause time, by the cyclic collector."""

    def __init__(self):
        self.counts, self.total_s, self.max_s, self._t = [0, 0, 0], 0.0, 0.0, 0.0
        gc.callbacks.append(self._on)

    def close(self):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        d = time.perf_counter() - self._t
        self.counts[info["generation"]] += 1
        self.total_s += d
        self.max_s = max(self.max_s, d)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, benchmark=None, traffic_dir=None,
         require_chip=True) -> int:
    args = parse(argv)
    parts = {}

    @contextlib.contextmanager
    def part(name):
        t = time.monotonic()
        yield
        parts[name] = parts.get(name, 0.0) + time.monotonic() - t

    benchmark = benchmark or bench.load_benchmark()
    cell = bench.load_cell(benchmark, args.workload, **(
        {"traffic_dir": traffic_dir} if traffic_dir else {}))
    with part("import_jax"):
        import jax
    with part("backend"):
        devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"lutbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    from repro.xla_env import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCount()
    drv = bench.driver(cell.traffic["kind"])
    # a traced run measures the first TRACE_SECONDS of its window, so
    # that its trace stays some tens of MB
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    with tempfile.TemporaryDirectory(prefix="lutbench-") as workdir:
        st = drv.setup(cell, args.seed, seconds, workdir, part)
    setup_compiles = (compiles.requests, compiles.hits)
    tracer = Tracer(os.path.join(TRACE_DIR, args.workload)
                    if args.trace else None)
    measure.quiet_gc()        # the window's own call then finds no work
    pauses = GcPauses()
    m = drv.window(st, seconds, tracer)
    pauses.close()
    setup_s = m.t_start - T_START
    window_compiles = compiles.requests - setup_compiles[0]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    drv.close(st)
    checks = drv.check(st, m)
    correct = all(v <= limit for v, limit in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    m.device_kind = devices[0].device_kind
    result = {"correct": correct, "attempted": m.attempted,
              "failed": m.failed}
    if args.trace:
        import trace_reduce

        m.trace = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.latest_xplane(tracer.log_dir)))
        read = bench.readers(cell.per_layer)
        units = {x["name"]: x["unit"] for x in cell.per_layer}
        metrics = {}
        for name, fn in read.items():
            v = fn(m)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        device.update(busy_s=m.trace.busy_s, window_s=m.trace.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [list(x) for x in m.trace.device_ops],
            "idle_gaps": [list(x) for x in m.trace.idle_gaps]}
    else:
        values = dict(drv.end_to_end(m), setup_s=setup_s)
        result["metrics"] = {x["name"]: {"value": values[x["name"]],
                                         "unit": x["unit"]}
                             for x in cell.end_to_end}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    print("setup_s parts: " + ", ".join(f"{k} {v:.3f}"
                                        for k, v in parts.items())
          + f"; programs compiled {setup_compiles[0]} "
          f"({setup_compiles[1]} from the persistent cache), "
          f"{window_compiles} in the window", file=sys.stderr)
    print(f"window GC: collections by generation {pauses.counts}, paused "
          f"{pauses.total_s * 1e3:.1f} ms in all, longest "
          f"{pauses.max_s * 1e3:.1f} ms", file=sys.stderr)
    if m.stalls is not None:
        print(f"window opened at monotonic {m.t_start:.3f} s; generator "
              f"stalls over {measure.STALL_S * 1e3:.0f} ms: " + (", ".join(
                  f"at {t:.3f} s {w * 1e3:.1f} ms" for t, w in m.stalls)
                  or "none"), file=sys.stderr)
    print(f"{args.workload} seed {args.seed}: {device['kind']} x"
          f"{device['count']}, window {m.window_s:.3f} s, "
          f"attempted {m.attempted}, failed {m.failed}", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
