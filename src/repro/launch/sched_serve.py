"""Scheduler-as-a-service launcher: serve channel-scheduling decisions.

Stands up a multi-tenant ``SchedServer`` (one compiled step for the whole
tenant pool — see ``repro.sim.serve``), joins ``--tenants`` concurrent FL
jobs, measures pipelined-vs-synchronous saturated throughput at equal
batch size, then replays Poisson request traffic through the pipelined
``serve_stream`` loop (autosized slot batches, churn interleaved with
in-flight steps) and reports p50/p99/p999 decision latency, queue depth,
batch occupancy and decisions/sec, and the service's own split of that
latency (``stats()``: queue wait and in flight, p50/p99), which it counts
while a profiler session is on (for example the launcher run under
``jax.profiler.trace``).

Usage:
  PYTHONPATH=src python -m repro.launch.sched_serve --tenants 256 --slots 64
  PYTHONPATH=src python -m repro.launch.sched_serve --tenants 64 --requests 512
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bandits import GLRCUCB
from repro.sim import SchedServer, ServeRequest
from repro.sim.serve import latency_quantile
from repro.utils.compile_cache import enable_compile_cache


def saturated_throughput(server, tenant_ids, states, keys, n_req: int):
    """Max decisions/sec: dispatch back-to-back full batches, block before
    reading the clock."""
    n_ten = len(tenant_ids)
    t0 = time.perf_counter()
    for start in range(0, n_req, server.slots):
        ids = range(start, min(start + server.slots, n_req))
        server.serve([ServeRequest(tenant_ids[j % n_ten],
                                   states[(j // n_ten) % states.shape[0],
                                          j % n_ten],
                                   keys[j]) for j in ids])
    jax.block_until_ready(server._state)
    return n_req / (time.perf_counter() - t0)


def _request(tenant_ids, states, keys, j):
    n_ten = len(tenant_ids)
    return ServeRequest(tenant_ids[j % n_ten],
                        states[(j // n_ten) % states.shape[0], j % n_ten],
                        keys[j])


def pipelined_throughput(server, tenant_ids, states, keys, n_req: int,
                         autosize: bool = False):
    """Max decisions/sec through ``serve_stream``: same request trace and
    step batch size as ``saturated_throughput`` (``autosize=False`` pins
    the slot batch so pipelined-vs-sync is an apples-to-apples overlap
    measurement), but host packing and result conversion overlap the
    in-flight device step instead of blocking on it."""
    t0 = time.perf_counter()
    src = (_request(tenant_ids, states, keys, j) for j in range(n_req))
    for _ in server.serve_stream(src, autosize=autosize):
        pass
    jax.block_until_ready(server._state)
    return n_req / (time.perf_counter() - t0)


def pipelined_poisson_episode(server, tenant_ids, states, keys, arrivals,
                              churn_stride: int = 0, churn_hp=None,
                              autosize: bool = True):
    """Poisson replay through the pipelined ``serve_stream`` loop; returns
    ``(latencies_s, wall_s, churn_events, queue_depths)``.

    The arrival process feeds a lazy generator: requests whose arrival time
    has passed are yielded to the stream; when the arrival queue runs dry a
    ``None`` flush marker dispatches whatever is pending as a short
    (autosized) step rather than waiting for a full batch.  Churn
    (``leave``+``join`` every ``churn_stride`` full-batch-equivalents of
    yielded requests — the same cadence as the synchronous episode's
    per-step stride) runs as a generator side effect, interleaved with
    in-flight device steps.
    ``queue_depths`` samples the arrived-but-undispatched backlog at every
    yield — the signal the autosizer reacts to.  Latency for request j is
    retire time (the stream yielding its assignment) minus ``arrivals[j]``:
    one-step pipeline latency is part of the measured cost, not hidden.
    """
    n_req = len(arrivals)
    n_ten = len(tenant_ids)
    lat = np.empty(n_req)
    depths: list = []
    churn_events = 0
    churn_ptr = 0
    t0 = time.perf_counter()

    def source():
        nonlocal churn_events, churn_ptr
        nxt = 0
        while nxt < n_req:
            now = time.perf_counter() - t0
            arrived = nxt
            while arrived < n_req and arrivals[arrived] <= now:
                arrived += 1
            if arrived == nxt:
                # nothing new: flush pending work, then wait out the gap
                yield None
                now = time.perf_counter() - t0
                if arrivals[nxt] > now:
                    time.sleep(min(arrivals[nxt] - now, 1e-3))
                continue
            depths.append(arrived - nxt)
            j = nxt
            nxt += 1
            yield _request(tenant_ids, states, keys, j)
            if churn_stride and (j + 1) % (churn_stride * server.slots) == 0:
                tid = tenant_ids[churn_ptr % n_ten]
                churn_ptr += 1
                server.leave(tid)
                server.join(tid, hp=churn_hp)
                churn_events += 1

    for i, _asg in server.serve_stream(source(), autosize=autosize):
        lat[i] = (time.perf_counter() - t0) - arrivals[i]
    jax.block_until_ready(server._state)
    wall = time.perf_counter() - t0
    return lat, wall, churn_events, np.asarray(depths)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=256)
    ap.add_argument("--slots", type=int, default=64,
                    help="requests batched per serving step")
    ap.add_argument("--channels", type=int, default=16)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--history", type=int, default=256)
    ap.add_argument("--requests", type=int, default=0,
                    help="episode length (default: 8 rounds per tenant)")
    ap.add_argument("--load", type=float, default=0.8,
                    help="offered Poisson load as a fraction of saturated "
                         "throughput")
    ap.add_argument("--churn-stride", type=int, default=16,
                    help="evict+readmit one tenant every this many steps "
                         "(0 = no churn)")
    args = ap.parse_args()
    enable_compile_cache()

    sched = GLRCUCB(args.channels, args.clients, history=args.history,
                    detector_stride=5, split_grid="auto")
    server = SchedServer(sched, capacity=args.tenants, slots=args.slots)
    print(f"[sched-serve] {sched.name}: N={args.channels} M={args.clients} "
          f"H={args.history}; capacity={args.tenants} slot_batch={args.slots} "
          f"({server.compiles} compiles, {server.compile_s:.1f}s)")

    key = jax.random.PRNGKey(0)
    tenant_ids = [f"job-{i}" for i in range(args.tenants)]
    for i, tid in enumerate(tenant_ids):
        server.join(tid, key=jax.random.fold_in(key, i),
                    hp={"gamma": 0.8 + 0.4 * i / args.tenants})
    print(f"[sched-serve] joined {len(server.tenants)} tenants "
          f"(compiles still {server.stats()['compiles']})")

    n_req = args.requests or args.tenants * 8
    rounds = 32
    means = jax.random.uniform(key, (args.tenants, args.channels),
                               minval=0.15, maxval=0.9)
    states = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(key, 1), means[None],
        (rounds, args.tenants, args.channels)), np.float32)
    keys = np.asarray(jax.random.split(jax.random.fold_in(key, 2), n_req))

    server.warm()   # precompile the autosize ladder: resizes cost 0 compiles
    warm = min(n_req, 4 * args.slots)
    rate = saturated_throughput(server, tenant_ids, states, keys, warm)
    pipe_n = min(n_req, 16 * args.slots)
    pipe_rate = pipelined_throughput(server, tenant_ids, states, keys, pipe_n)
    print(f"[sched-serve] saturated: sync {rate:.0f} decisions/s, pipelined "
          f"{pipe_rate:.0f} decisions/s ({pipe_rate / rate:.2f}x, equal "
          f"batch={args.slots})")

    lam = args.load * rate
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / lam, size=n_req))

    st0 = server.stats()
    lat, wall, churn, depths = pipelined_poisson_episode(
        server, tenant_ids, states, keys, arrivals,
        churn_stride=args.churn_stride)
    p50, p99, p999 = np.percentile(lat, [50, 99, 99.9]) * 1e3
    st = server.stats()
    print(f"[sched-serve] Poisson load {args.load:.0%} ({lam:.0f} req/s): "
          f"served {n_req} requests in {wall:.2f}s "
          f"({n_req / wall:.0f} decisions/s), latency p50={p50:.2f}ms "
          f"p99={p99:.2f}ms p999={p999:.2f}ms, queue depth "
          f"mean={depths.mean():.1f} max={depths.max()}, churn_events={churn}, "
          f"batch_occupancy={st['batch_occupancy']:.2f}, sizes_used="
          f"{st['sizes_used']}, compiles={st['compiles']}")
    # the episode's share of the service's cumulative histograms, which
    # count only while a profiler session is on
    split = []
    for name, key in (("queue wait", "queue_wait_counts"),
                      ("in flight", "inflight_counts")):
        counts = np.subtract(st[key], st0[key])
        p50, p99 = (latency_quantile(st["latency_edges_s"], counts, q)
                    for q in (50, 99))
        split.append(f"{name} p50={p50 * 1e3:.2f}ms p99={p99 * 1e3:.2f}ms"
                     if p50 is not None else f"{name} not counted")
    print("[sched-serve] the service's split of that latency: "
          + ", ".join(split) + " (counted while a profiler session is on)")


if __name__ == "__main__":
    main()
