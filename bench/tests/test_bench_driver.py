"""The traffic generator and the client-side metric arithmetic, on a fake
engine and a fake clock."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.harness import driver, traffic  # noqa: E402

OPEN = {"loop": "open", "rate_per_s": 4.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                   "min": 32, "max": 768},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.5,
                   "min": 16, "max": 256}}
CLOSED = {"loop": "closed", "clients": 2, "requests": 5,
          "prompt": {"dist": "uniform", "min": 10, "max": 20},
          "output": {"dist": "fixed", "value": 3}}
BIG_SEED = 2 ** 31 + 12345


def _key(reqs):
    return [(r.prompt.tolist(), r.max_tokens, r.due) for r in reqs]


def test_same_seed_same_requests():
    a = traffic.generate(OPEN, BIG_SEED, 30, 32064)
    b = traffic.generate(OPEN, BIG_SEED, 30, 32064)
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.generate(OPEN, BIG_SEED + 1, 30, 32064))


def test_seeds_share_the_schedule_not_the_tokens():
    """Two seeds serve the same set of lengths and arrival gaps, each in
    its own order, with other token ids."""
    a = traffic.generate(OPEN, 1, 30, 32064)
    b = traffic.generate(OPEN, 2, 30, 32064)
    assert len(a) == len(b) == 120

    def gaps(reqs):
        due = [r.due for r in reqs]
        return np.diff(due)

    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.max_tokens for r in a] != [r.max_tokens for r in b]
    assert list(gaps(a)) != list(gaps(b))
    assert any((r.prompt[:32] != q.prompt[:32]).any() for r, q in zip(a, b))
    assert a[0].due == 0.0 and b[0].due == 0.0
    assert all(32 <= len(r.prompt) <= 768 and 16 <= r.max_tokens <= 256
               for r in a)
    # Poisson gaps at quantiles: the arrivals span n / rate seconds, and
    # every seed's gaps are the same quantiles but its first
    for reqs in (a, b):
        g = gaps(reqs)
        assert reqs[-1].due + np.mean(g) == pytest.approx(120 / 4.0,
                                                          rel=0.05)
    assert len(set(np.round(gaps(a), 9)) ^ set(np.round(gaps(b), 9))) <= 2


def test_closed_loop_repeats_its_set():
    reqs = traffic.generate(CLOSED, 7, 10, 100)
    assert len(reqs) == 5 * traffic.CLOSED_PASSES
    first = sorted(len(r.prompt) for r in reqs[:5])
    assert first == sorted(len(r.prompt) for r in reqs[5:10])
    assert all(r.due == 0.0 for r in reqs)


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_strata_deal_one_length_of_each_band_per_round(seed):
    """Every round of ``strata`` requests holds one prompt length and one
    output length of each band; each pass is the whole set."""
    mix = dict(CLOSED, requests=8, strata=4,
               prompt={"dist": "uniform", "min": 10, "max": 49},
               output={"dist": "uniform", "min": 2, "max": 9})
    reqs = traffic.generate(mix, seed, 10, 100)
    whole = {k: sorted(traffic._quantiles(mix[k], 8))
             for k in ("prompt", "output")}

    def band(key, v):
        return whole[key].index(v) // 2

    for p in range(traffic.CLOSED_PASSES):
        one = reqs[8 * p:8 * p + 8]
        assert sorted(len(r.prompt) for r in one) == whole["prompt"]
        assert sorted(r.max_tokens for r in one) == whole["output"]
        for r in range(2):
            rnd = one[4 * r:4 * r + 4]
            assert sorted(band("prompt", len(q.prompt)) for q in rnd) == \
                [0, 1, 2, 3]
            assert sorted(band("output", q.max_tokens) for q in rnd) == \
                [0, 1, 2, 3]
    other = traffic.generate(mix, seed + 1, 10, 100)
    assert [len(r.prompt) for r in reqs] != [len(r.prompt) for r in other]


def test_strata_keep_the_set_when_rounds_do_not_divide_it():
    """120 lengths in 7 bands: the same lengths as one shuffle, every
    full round one of each band, the last round the rest."""
    plain = traffic.generate(OPEN, 5, 30, 32064)
    dealt = traffic.generate(dict(OPEN, strata=7), 5, 30, 32064)
    assert sorted(len(r.prompt) for r in plain) == \
        sorted(len(r.prompt) for r in dealt)
    out = traffic._deal(np.random.default_rng(5), np.arange(120), 7)
    assert sorted(out) == list(range(120))
    band = [next(b for b in range(7) if v < (b + 1) * 120 // 7) for v in out]
    for r in range(120 // 7):
        assert sorted(band[7 * r:7 * r + 7]) == list(range(7))
    assert len(band[7 * (120 // 7):]) == 120 % 7


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-6)


class FakeEngine:
    """Each step takes ``dt`` seconds and gives every admitted request one
    token; the first step after submission only prefills (no token)."""

    def __init__(self, clock, dt=0.1, n_slots=2):
        self.clock, self.dt = clock, dt
        self.n_slots = n_slots
        self.queue = []
        self.scheduler = SimpleNamespace(slots=[None] * n_slots,
                                         pending=self._pending)
        self.rid = 0

    def _pending(self):
        return bool(self.queue) or any(self.scheduler.slots)

    def submit(self, prompt, n):
        self.queue.append(SimpleNamespace(rid=self.rid, prompt=prompt, n=n))
        self.rid += 1
        return self.rid - 1

    def step(self):
        self.clock.t += self.dt
        done = []
        slots = self.scheduler.slots
        for i, st in enumerate(slots):
            if st is None:
                continue
            st.tokens.append(1)
            if len(st.tokens) == st.req.n:
                done.append(SimpleNamespace(rid=st.req.rid,
                                            tokens=list(st.tokens)))
                slots[i] = None
        for i in range(len(slots)):
            if slots[i] is None and self.queue:
                slots[i] = SimpleNamespace(req=self.queue.pop(0), tokens=[])
        return done


def _submit(engine, prompt, n):
    return engine.submit(prompt, n)


def _run(mix, reqs, seconds, dt=0.1, n_slots=2):
    clock = FakeClock()
    eng = FakeEngine(clock, dt, n_slots)
    win = driver.run(eng, _submit, reqs, mix, seconds, clock=clock,
                     sleep=clock.sleep)
    return win, driver.end_to_end(win)


def _reqs(dues, n=3):
    return [traffic.Request(i, np.zeros(4, np.int32), n, d)
            for i, d in enumerate(dues)]


def test_ttft_counts_from_due_time():
    # one slot, 0.1 s steps: request 0 is admitted by the step ending at
    # 0.1 and gets its first token at 0.2; request 1, due at 0.05, waits
    # for request 0's third token (0.4) and gets its first at 0.5
    win, m = _run({"loop": "open"}, _reqs([0.0, 0.05]), 2.0, n_slots=1)
    r0, r1 = win.served
    assert r0.token_times[0] - r0.due == pytest.approx(0.2)
    assert r1.token_times[0] - r1.due == pytest.approx(0.5 - 0.05)
    assert m["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([0.2, 0.45], 95))
    assert m["ttft_p50_ms"] == pytest.approx(
        1e3 * np.percentile([0.2, 0.45], 50))
    assert r1.submit - r1.due == pytest.approx(0.05)


def test_itl_takes_every_gap():
    win, m = _run({"loop": "open"}, _reqs([0.0, 0.0], n=4), 5.0)
    gaps = [b - a for s in win.served
            for a, b in zip(s.token_times, s.token_times[1:])]
    assert len(gaps) == m["n_gaps"] == 6
    assert m["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))


def test_tokens_per_s_counts_only_the_window():
    # 2 slots, 10 requests of 5 tokens, 0.1 s steps, a 1 s window: the
    # rest are served in the drain after the window and not counted
    win, m = _run({"loop": "open"}, _reqs([0.0] * 10, n=5), 1.0)
    inside = sum(1 for s in win.served for t in s.token_times
                 if win.start <= t <= win.end)
    total = sum(len(s.token_times) for s in win.served)
    assert total == 50 and win.drained
    assert inside < total
    assert m["tokens_per_s"] == pytest.approx(inside / 1.0)
    assert m["attempted"] == 10 and m["failed"] == 0


def test_requests_due_in_window_are_all_sent():
    win, m = _run({"loop": "open"}, _reqs([0.0, 0.3, 0.95, 1.5]), 1.0,
                  dt=0.5)
    assert m["attempted"] == 3
    assert {s.index for s in win.served} == {0, 1, 2}


def test_closed_loop_sends_next_on_finish():
    reqs = traffic.generate(CLOSED, 3, 2.0, 100)
    win, m = _run(CLOSED, reqs, 2.0, n_slots=2)
    first = win.served[:2]
    assert all(s.due == win.start for s in first)
    # a later request is due when an earlier one finished
    later = win.served[2]
    assert later.due == pytest.approx(min(s.token_times[-1]
                                          for s in first))
