"""The traffic generator: repeatable from a seed, the rate it is given,
Zipf-skewed users, candidates drawn as the mix says."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import traffic  # noqa: E402

CFG = {"n_items": 10_000, "n_cats": 100, "ctx_dim": 4}
MIX = {"rate_per_s": 400, "zipf_s": 1.1, "candidates": 32,
       "in_interest_share": 0.5, "max_burst": 16}
POP = 4096
BIG_SEED = 2**31 + 12345


def interests(seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(CFG["n_cats"], 5, replace=False)
                     for _ in range(POP)])


def sched(seed, seconds=10.0):
    return traffic.schedule(MIX, CFG, seconds, seed, interests())


def test_same_seed_same_traffic():
    a, b = sched(BIG_SEED), sched(BIG_SEED)
    for f in ("due", "users", "cand_items", "cand_cats", "ctx"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_other_seed_same_work_other_order():
    a, b = sched(7), sched(8)
    assert len(a) == len(b) == 4000
    assert not np.array_equal(a.users, b.users)


def test_rate_and_window():
    s = sched(3, seconds=20.0)
    assert len(s) == round(MIX["rate_per_s"] * 20.0)
    assert np.all(np.diff(s.due) >= 0)
    assert 0 <= s.due[0] and s.due[-1] < 20.0
    # Poisson arrivals: gaps are exponential with mean 1/rate
    gaps = np.diff(s.due)
    assert np.mean(gaps) == pytest.approx(1 / MIX["rate_per_s"], rel=0.05)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.1)


def test_zipf_shape_and_permuted_ids():
    rng = np.random.default_rng(1)
    n, s = 200_000, 1.1
    users = traffic.zipf_users(rng, n, POP, s)
    counts = np.sort(np.bincount(users, minlength=POP))[::-1]
    w = np.arange(1, POP + 1, dtype=float) ** -s
    want = w / w.sum()
    for r in (0, 1, 9, 99):
        assert counts[r] / n == pytest.approx(want[r], rel=0.1)
    other = traffic.zipf_users(np.random.default_rng(2), n, POP, s)
    assert np.argmax(np.bincount(users)) != np.argmax(np.bincount(other))


def test_candidates():
    s = sched(5)
    per_cat = CFG["n_items"] // CFG["n_cats"]
    np.testing.assert_array_equal(s.cand_items // per_cat, s.cand_cats)
    own = interests()[s.users]
    hit = (s.cand_cats[:, :, None] == own[:, None, :]).any(-1).mean()
    # half from the user's interests, and a few of the rest by chance
    assert 0.48 < hit < 0.55
    assert s.cand_items.dtype == np.int32 and s.ctx.dtype == np.float32
    assert set(np.unique(s.ctx)) <= {0.0, 1.0}


@pytest.mark.parametrize("now,start,end", [
    (0.05, 0, 3), (0.05, 1, 3), (10.0, 0, 16), (10.0, 20, 36), (0.0, 0, 0)])
def test_bursts_take_what_is_due_up_to_the_limit(now, start, end):
    due = np.array([0.01, 0.02, 0.03] + [0.1 + i for i in range(60)])
    due[3:] = np.linspace(0.1, 9.9, 60)
    assert traffic.bursts(due, now, start, 16) == end
