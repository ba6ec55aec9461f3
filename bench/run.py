"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run refuses to
start without a TPU, or with fewer chips than the cell asks for, and
never falls back to the CPU.  With ``--trace 0`` the result's metrics are
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and they are the cell's per-layer metrics, with the device's
busy time and the trace's breakdown.  Either way the outputs of the timed
path are compared with the configuration's plain reference, and the last
line of standard output is the result as one JSON object.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``
(or where ``JAX_COMPILATION_CACHE_DIR`` says), so only the first run of a
cell in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from bench import harness
    harness.prepare(ROOT)
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    devices = harness.require_chips(int(cell["chips"]))

    cfg = bench.config(cell["config"])
    ctx = argparse.Namespace(
        bench=bench, cell=cell, cfg=cfg, mix=bench.traffic(cell["traffic"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, t_start=T_START, log=harness.log)
    out = bench.kind(cfg["kind"]).run(ctx)

    if args.trace:
        metrics = {}
        obs = out["obs"]
        for m in bench.per_layer(cell["name"]):
            value = bench.metric_reader(m["name"]).read(obs, m)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace = obs["trace"]
        out["device"]["busy_s"] = trace.mean_busy_s()
        out["device"]["window_s"] = trace.window_s
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_by_host_activity(10)}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench.end_to_end(cell["name"])}
        breakdown = None
    checks = out["checks"]
    result = {"correct": harness.correct(checks), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if breakdown is not None:
        result["breakdown"] = breakdown
    harness.emit(result, checks)


if __name__ == "__main__":
    main()
