"""Closed-loop FL-job traffic for the scheduling service.

Adapted from the repository's ``pipelined_poisson_episode``
(``repro.launch.sched_serve``): requests feed ``serve_stream`` from a lazy
generator, a ``None`` flush marker dispatches what is pending when nothing
else is due, and each request is timed from when it was due.  What
differs is the traffic.  A real FL job cannot queue a second schedule
request before the first is answered: its next request carries the
channel outcome of the round it was just scheduled for.  So each job here
is a closed loop with one request outstanding:

    request due -> answer (assignment) -> think (local training and
    upload) -> next request due

A job's think times are exponential around its own mean.  The means are
one fixed set for every seed, the quantiles of a lognormal of the mix's
``think_sigma`` scaled by ``think_scale_s``, dealt to the jobs in an order
drawn from the seed: a few fast jobs send most requests, and every seed
offers the same load.

Jobs come and go on their own clock, their round count: a job that has
had ``job_rounds`` rounds scheduled leaves the service, and a fresh job
with the same think-time mean joins in its place (``job_rounds`` 0: jobs
never leave).  The first jobs are met part-way through their runs, with
rounds left drawn from the seed between 1 and ``job_rounds``, so that
leaves are spread over the run from its start.  How often jobs churn
therefore follows from the traffic alone, never from how the service
batches its steps.

Each request carries what the FL trainers' protocol sends: the job's
realized (N,) channel vector for this round, drawn from its own
piecewise-stationary Bernoulli channels (every channel's mean redrawn
every ``segment_rounds`` rounds), a round key, the (M,) client
contributions, and the job's (M,) AoI, which the job updates from the
answer (a client whose assigned channel succeeded resets to 1).

Everything a job sends is a function of the seed, its place among the
jobs and its generation there (its own random stream), its round number,
and the answers it got; timing only changes the interleaving of jobs.  Every request and answer is recorded,
so that a plain reference can replay each job afterwards.
"""
from __future__ import annotations

import heapq
import time
from collections import deque
from statistics import NormalDist

import numpy as np

CHUNK = 64          # rounds of a job's inputs drawn at once


def think_means(n_jobs, sigma, scale_s, seed):
    """One mean think time per job: lognormal quantiles, seed-dealt."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n_jobs)
                  for i in range(n_jobs)])
    means = scale_s * np.exp(sigma * z)
    return np.random.default_rng([seed, 0x7A1C]).permutation(means)


def first_rounds_left(n_jobs, job_rounds, seed):
    """Rounds left to each of the first jobs: seed-drawn in
    [1, job_rounds], or 0 (never leaves) where ``job_rounds`` is 0."""
    if not job_rounds:
        return np.zeros((n_jobs,), np.int64)
    return np.random.default_rng([seed, 0x11FE]).integers(
        1, job_rounds + 1, n_jobs)


class Job:
    """One FL job: its random stream, its AoI, and what it sent and got.

    ``place`` and ``generation`` key its random stream: the job at a place
    that follows one that left is its next generation.  ``lifetime`` is
    the number of rounds after which it leaves (0: never)."""

    def __init__(self, jid, place, generation, lifetime, seed, think_mean,
                 mix, n_channels, n_clients):
        self.jid = jid
        self.place, self.generation = place, generation
        self.lifetime = lifetime
        self.think_mean = think_mean
        self.rng = np.random.default_rng([seed, 0x10B, place, generation])
        self.mix = mix
        self.n, self.m = n_channels, n_clients
        self.rounds = 0                 # requests sent
        self.means = None
        self.aoi = np.ones((n_clients,), np.float32)
        self.chunks = []                # [(rewards, keys, contrib, think,
                                        #   channel up as bool)]
        self.aois = []                  # AoI sent with each request
        self.answers = []               # assignment got for each request

    def _draw_chunk(self):
        mix, rng = self.mix, self.rng
        base = len(self.chunks) * CHUNK
        seg = int(mix["segment_rounds"])
        means = np.empty((CHUNK, self.n), np.float64)
        for i in range(CHUNK):
            if (base + i) % seg == 0 or self.means is None:
                self.means = rng.uniform(mix["channel_mean_low"],
                                         mix["channel_mean_high"], self.n)
            means[i] = self.means
        up = rng.random((CHUNK, self.n)) < means
        rewards = up.astype(np.float32)
        keys = rng.integers(0, 2 ** 32, size=(CHUNK, 2), dtype=np.uint32)
        contrib = rng.uniform(mix["contrib_low"], 1.0,
                              (CHUNK, self.m)).astype(np.float32)
        think = rng.exponential(1.0, CHUNK) * self.think_mean
        self.chunks.append((rewards, keys, contrib, think, up))

    def _row(self, r):
        c, i = divmod(r, CHUNK)
        while c >= len(self.chunks):
            self._draw_chunk()
        return self.chunks[c], i

    def request(self, make):
        """The next request, built by ``make(tenant, rewards, key,
        contrib, aoi)``."""
        (rewards, keys, contrib, _, _), i = self._row(self.rounds)
        self.rounds += 1
        self.aois.append(self.aoi)
        return make(self.jid, rewards[i], keys[i], contrib[i], self.aoi)

    def think(self, r):
        """The think time before request ``r`` is due."""
        (_, _, _, think, _), i = self._row(r)
        return float(think[i])

    def done(self):
        """Whether the job has had all its rounds and leaves."""
        return bool(self.lifetime) and len(self.answers) >= self.lifetime

    def answer(self, assignment):
        """Take the answer to the outstanding request; returns the think
        time before the next one is due."""
        r = len(self.answers)
        c, i = divmod(r, CHUNK)
        self.answers.append(assignment)
        aoi = self.aoi + np.float32(1.0)
        aoi[self.chunks[c][4][i].take(assignment, mode="clip")] = 1.0
        self.aoi = aoi
        return self.think(r + 1)

    def history(self):
        """What the job sent and got, request by request."""
        k = len(self.answers)
        cat = [np.concatenate(x)[:k] for x in zip(*self.chunks)]
        return {"rewards": cat[0], "keys": cat[1], "contrib": cat[2],
                "aoi": np.asarray(self.aois[:k], np.float32).reshape(k, self.m),
                "served": np.asarray(self.answers, np.int32).reshape(k, self.m)}


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class ClosedLoop:
    """Drive ``server.serve_stream`` with ``mix["jobs"]`` closed-loop jobs.

    ``run(warmup_s, seconds)`` runs the jobs for ``warmup_s`` (not
    measured), then measures for ``seconds``: requests due inside the
    window are timed from their due time to their answer, and answers
    returned inside the window are counted.  At the window's end no new
    request is sent, and every outstanding request is answered before
    ``run`` returns.  ``span(name)`` opens a host span (a profiler
    annotation in a traced run).
    """

    def __init__(self, server, mix, seed, n_channels, n_clients, make_request,
                 span=None, clock=time.perf_counter):
        self.server = server
        self.mix = mix
        self.seed = seed
        self.n, self.m = n_channels, n_clients
        self.make = make_request
        self.span = span or (lambda name: NO_SPAN)
        self.clock = clock
        n_jobs = int(mix["jobs"])
        self.job_rounds = int(mix["job_rounds"])
        means = think_means(n_jobs, float(mix["think_sigma"]),
                            float(mix["think_scale_s"]), seed)
        left = first_rounds_left(n_jobs, self.job_rounds, seed)
        self.on_window = None           # called with True / False at the
                                        # window's start / end
        self.jobs = {}
        self.next_id = 0
        self.left_jobs = 0
        for place in range(n_jobs):
            self._join(place, 0, int(left[place]), float(means[place]))

    def _join(self, place, generation, lifetime, think_mean):
        jid = self.next_id
        self.next_id += 1
        self.jobs[jid] = Job(jid, place, generation, lifetime, self.seed,
                             think_mean, self.mix, self.n, self.m)
        self.server.join(jid)
        return jid

    def _replace(self, jid):
        """Job ``jid``, which has no request out, leaves; the next
        generation at its place joins with the same think-time mean."""
        old = self.jobs[jid]
        self.server.leave(jid)
        self.left_jobs += 1
        return self._join(old.place, old.generation + 1, self.job_rounds,
                          old.think_mean)

    def run(self, warmup_s, seconds):
        server, clock, span = self.server, self.clock, self.span
        heap = []                       # (due, jid): jobs thinking
        leaving = deque()               # jobs done, to leave at the next pull
        t0 = clock()
        for jid, job in self.jobs.items():
            heapq.heappush(heap, (t0 + job.think(0), jid))
        w0, w1 = t0 + warmup_s, t0 + warmup_s + seconds
        self.w0 = w0
        sent = []                       # per stream index: (jid, due, sent_at)
        self.lat, self.gen_lag = [], []
        self.answered_in_window = 0
        self.window_marks = {}
        state = {"outstanding": 0, "window": None}

        def source():
            while True:
                now = clock()
                if now >= w0 and state["window"] is None:
                    state["window"] = span("bench.window")
                    state["window"].__enter__()
                    self.window_marks["start"] = dict(server.stats())
                    if self.on_window:
                        self.on_window(True)
                if now >= w1:
                    self.window_marks["end"] = dict(server.stats())
                    if self.on_window:
                        self.on_window(False)
                    if state["window"] is not None:
                        state["window"].__exit__(None, None, None)
                    return
                if leaving:
                    with span("bench.churn"):
                        new = self._replace(leaving.popleft())
                        heapq.heappush(heap, (now + self.jobs[new].think(0),
                                              new))
                    continue
                if heap and heap[0][0] <= now:
                    with span("bench.generate"):
                        due, jid = heapq.heappop(heap)
                        rq = self.jobs[jid].request(self.make)
                        sent.append((jid, due, now))
                        state["outstanding"] += 1
                    yield rq
                elif state["outstanding"]:
                    yield None          # dispatch what is pending / retire
                else:
                    with span("bench.sleep"):
                        nxt = heap[0][0] if heap else w1
                        gap = min(nxt, w1) - clock()
                        if gap > 0:
                            time.sleep(min(gap, 1e-3))

        for i, asg in server.serve_stream(source(), autosize=True):
            now = clock()
            with span("bench.retire"):
                jid, due, at = sent[i]
                state["outstanding"] -= 1
                job = self.jobs[jid]
                think = job.answer(np.asarray(asg))
                if w0 <= due < w1:
                    self.lat.append(now - due)
                    self.gen_lag.append(at - due)
                if w0 <= now < w1:
                    self.answered_in_window += 1
                if job.done():
                    leaving.append(jid)
                else:
                    heapq.heappush(heap, (now + think, jid))
        self.sent = len(sent)
        self.answered = sum(len(j.answers) for j in self.jobs.values())
        self.window_s = seconds
        self.window_requests = sum(1 for _, due, _ in sent if w0 <= due < w1)
        return self
