"""Find the scheduling service's capacity and the think-time scale for a
closed-loop cell at a share of it: a one-off sweep, run on the chip.

    python3 bench/sweep_knee.py --config serve256 --traffic think \
        --scales 0,0.02,0.04,0.06,0.08 --seconds 4

Each point runs the mix with ``think_scale_s`` set to one scale (0: zero
think, the saturated rate) and prints decisions/s and the latency tail.
The result is recorded in PERF.md; the chosen scale is written into the
traffic file by hand.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--scales", required=True)
    ap.add_argument("--seconds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 7)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import numpy as np
    from bench import harness
    harness.prepare(ROOT)
    harness.require_chips(1)
    bench = harness.Benchmark(ROOT)
    cfg = bench.config(args.config)
    serve = bench.kind(cfg["kind"])
    for scale in (float(x) for x in args.scales.split(",")):
        mix = dict(bench.traffic(args.traffic), think_scale_s=scale)
        loop, server, _ = serve.drive(cfg, mix, args.seed, args.seconds)
        lat = np.asarray(loop.lat) * 1e3
        st = server.stats()
        print(json.dumps({
            "think_scale_s": scale,
            "decisions_per_s": loop.answered_in_window / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "requests": int(lat.size),
            "gen_lag_p99_ms": float(np.percentile(loop.gen_lag, 99)) * 1e3,
            "batch_occupancy": st["batch_occupancy"],
            "sizes_used": st["sizes_used"]}), flush=True)


if __name__ == "__main__":
    main()
