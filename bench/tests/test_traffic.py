"""The generator gives every seed the same work in another order."""
import collections

import numpy as np

from harness.traffic import prefill_buckets, schedule

CHAT = {"arrivals": "open_loop",
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "min": 32, "max": 2048},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 16, "max": 512}}
LONG = {"arrivals": "backlog", "blocks": 3,
        "prompt": {"dist": "uniform", "min": 2048, "max": 6144},
        "output": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                   "min": 1024, "max": 4096}}


def _work(reqs):
    return (collections.Counter(len(r.prompt) for r in reqs if r.in_window),
            collections.Counter(r.max_new for r in reqs if r.in_window))


def test_seeds_share_the_work():
    a = schedule(CHAT, 7, window_s=30, max_len=3072, vocab=1000, slots=64,
                 rate_per_s=5)
    b = schedule(CHAT, 2**33 + 9, window_s=30, max_len=3072, vocab=1000,
                 slots=64, rate_per_s=5)
    assert _work(a) == _work(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    wa = [r for r in a if r.in_window]
    assert len(wa) == 150 and 0 < wa[0].due and wa[-1].due < 30
    assert all(r.due >= 30 for r in a if not r.in_window)


def test_same_seed_same_requests():
    a = schedule(CHAT, 3, window_s=10, max_len=3072, vocab=1000, slots=8,
                 rate_per_s=4)
    b = schedule(CHAT, 3, window_s=10, max_len=3072, vocab=1000, slots=8,
                 rate_per_s=4)
    assert all(x.due == y.due and np.array_equal(x.prompt, y.prompt)
               and x.max_new == y.max_new for x, y in zip(a, b))


def test_lengths_fit_the_context():
    reqs = schedule(CHAT, 1, window_s=30, max_len=2048, vocab=1000,
                    slots=8, rate_per_s=5)
    assert all(len(r.prompt) + r.max_new <= 2048 for r in reqs)
    assert min(r.max_new for r in reqs) >= 16


def test_backlog_blocks_fill_alike():
    a = schedule(LONG, 1, window_s=30, max_len=10240, vocab=1000, slots=16)
    b = schedule(LONG, 2, window_s=30, max_len=10240, vocab=1000, slots=16)
    assert len(a) == 48 and all(r.due == 0 for r in a)
    first = lambda rs: sorted(len(r.prompt) for r in rs[:16])
    assert first(a) == first(b)
    assert 2048 <= min(first(a)) and max(first(a)) <= 6144


def test_buckets_as_the_engine_pads():
    reqs = schedule(CHAT, 1, window_s=30, max_len=3072, vocab=1000,
                    slots=8, rate_per_s=5)
    b = prefill_buckets(reqs, 64, 3072)
    assert all(k % 64 == 0 and k - 64 < v <= k for k, v in b.items())
