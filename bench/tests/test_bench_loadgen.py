"""The closed-loop job generator against a stand-in service that answers
every request at once (CPU only, no JAX)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.loadgen import ClosedLoop, first_rounds_left, think_means  # noqa: E402

N, M = 5, 3


class InstantServer:
    """Joins, leaves and answers; it never reports a step."""

    def __init__(self):
        self.joined, self.left, self.live = [], [], set()

    def join(self, tenant):
        assert tenant not in self.live
        self.joined.append(tenant)
        self.live.add(tenant)

    def leave(self, tenant):
        self.live.remove(tenant)
        self.left.append(tenant)

    def stats(self):
        return {"steps": 0}

    def serve_stream(self, source, autosize=True):
        i = 0
        for rq in source:
            if rq is None:
                continue
            assert rq[0] in self.live, "a request from a job that left"
            yield i, np.arange(M) % N
            i += 1


def _mix(**kw):
    mix = {"jobs": 6, "think_sigma": 1.0, "think_scale_s": 1e-4,
           "job_rounds": 7, "segment_rounds": 4, "channel_mean_low": 0.1,
           "channel_mean_high": 0.9, "contrib_low": 0.05}
    mix.update(kw)
    return mix


def _loop(mix, seed=2 ** 31 + 11):
    server = InstantServer()
    loop = ClosedLoop(server, mix, seed, N, M, lambda *a: a)
    return loop.run(0.05, 0.2), server


def test_jobs_leave_after_their_own_rounds():
    loop, server = _loop(_mix())
    assert loop.left_jobs > 0 and len(server.left) == loop.left_jobs
    for jid in server.left:
        job = loop.jobs[jid]
        assert len(job.answers) == job.lifetime
    # every place keeps one job at a time, its generations in order
    places = {}
    for jid in server.joined:
        job = loop.jobs[jid]
        places.setdefault(job.place, []).append(job.generation)
    assert sorted(places) == list(range(6))
    assert all(g == list(range(len(g))) for g in places.values())
    # a later generation runs the whole of job_rounds
    assert all(loop.jobs[j].lifetime == 7 for j in server.joined[6:])
    assert loop.answered == loop.sent


def test_the_first_jobs_are_met_part_way_and_the_load_is_the_seeds():
    left = first_rounds_left(256, 500, 2 ** 31 + 5)
    assert left.min() >= 1 and left.max() <= 500 and len(set(left)) > 100
    assert (left == first_rounds_left(256, 500, 2 ** 31 + 5)).all()
    assert not first_rounds_left(4, 0, 1).any()
    a, b = think_means(256, 1.0, 0.03, 1), think_means(256, 1.0, 0.03, 2)
    assert sorted(a) == pytest.approx(sorted(b)) and not np.allclose(a, b)


def test_jobs_never_leave_where_job_rounds_is_zero():
    loop, server = _loop(_mix(job_rounds=0))
    assert loop.left_jobs == 0 and not server.left
    assert len(server.joined) == 6


def test_what_a_job_sends_is_its_places_and_generations():
    """Two runs of one seed send the same inputs from each (place,
    generation), however the jobs interleave."""
    def sent(loop):
        out = {}
        for job in loop.jobs.values():
            h = job.history()
            k = min(len(h["rewards"]), 3)
            out[(job.place, job.generation)] = h["rewards"][:k]
        return out
    a, _ = _loop(_mix())
    b, _ = _loop(_mix())
    common = set(sent(a)) & set(sent(b))
    assert len(common) >= 6
    for key in common:
        x, y = sent(a)[key], sent(b)[key]
        k = min(len(x), len(y))
        assert (x[:k] == y[:k]).all()
