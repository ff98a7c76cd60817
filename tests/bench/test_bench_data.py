"""Inputs made from the seed: the same seed gives the same inputs, another
seed other inputs, and every seed the same amount of work."""
from __future__ import annotations

import numpy as np
import pytest

from bench import data, episodes
from bench.drivers import served

from tinybench import tiny_config, tiny_mixes, tiny_spot_config

SEEDS = (0, 7, 2**31 + 12345, 2**33 + 1)


def _models(seed):
    return data.tenant_models(tiny_config(), seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_models_repeat_per_seed(seed):
    a, b = _models(seed), _models(seed)
    for x, y in zip(a, b):
        for k in ("beta", "gamma", "n", "rho", "pi"):
            np.testing.assert_array_equal(x[k], y[k])


def test_models_change_with_seed():
    a, b = _models(1), _models(2)
    assert not np.array_equal(a[0]["beta"], b[0]["beta"])
    # tenants of one seed are fitted from their own benchmark noise
    assert not np.array_equal(a[0]["beta"], a[1]["beta"])


def test_models_are_sound():
    cfg = tiny_config()
    for m in _models(3):
        mu = sum(p["count"] for p in cfg["platforms"])
        assert m["beta"].shape == (mu, cfg["n_tasks"])
        assert (m["beta"] > 0).all() and (m["gamma"] >= 0).all()
        lo, hi = data.budget_range(m)
        assert 0 < lo <= hi


def test_fit_recovers_the_true_models():
    cfg = dict(tiny_config(), bench_noise_sigma=1e-6, bench_jitter_share=0.0)
    steps = data.task_steps(cfg, data.rng(5, data.TASKS))
    beta, gamma = data.true_models(cfg, steps)
    n = np.full(cfg["n_tasks"], float(cfg["n_paths"]))
    fb, fg = data.fitted_models(cfg, beta, gamma, n, data.rng(5, data.FIT))
    # with the lognormal noise off, only the ~1 ms timer jitter is left
    np.testing.assert_allclose(fb * n + fg, beta * n + gamma, rtol=1e-2)


def _plan(seed):
    mix = tiny_mixes()["tiny_replans"]
    return served.schedule(_models(seed), mix, seed, 3.0)


@pytest.mark.parametrize("seed", SEEDS)
def test_request_schedule_repeats_per_seed(seed):
    a, b = _plan(seed), _plan(seed)
    assert [q["due"] for q in a] == [q["due"] for q in b]
    assert [q["cap"] for q in a] == [q["cap"] for q in b]


def test_request_schedule_same_work_other_order():
    a, b = _plan(11), _plan(12)
    assert len(a) == len(b)
    gaps = [np.sort(np.diff([0.0] + [q["due"] for q in p])) for p in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert [q["due"] for q in a] != [q["due"] for q in b]
    n_dead = [sorted(int(q["dead"].sum()) if q["dead"] is not None else 0
                     for q in p) for p in (a, b)]
    assert n_dead[0] == n_dead[1]
    assert all(q["due"] < 3.0 for q in a)


def test_request_caps_are_feasible():
    for q, m in ((q, _models(4)[q["tenant"]]) for q in _plan(4)):
        lo, hi = data.budget_range(m, q["dead"])
        assert lo <= q["cap"] <= hi


def _episode(seed, i=0):
    cfg = tiny_spot_config()
    return episodes.generate(("k0", "k1", "k2"), cfg,
                             data.rng(seed, data.EPISODES, i))


@pytest.mark.parametrize("seed", SEEDS)
def test_episodes_repeat_per_seed(seed):
    assert _episode(seed) == _episode(seed)


def test_episodes_change_with_seed_and_stay_applicable():
    assert _episode(1) != _episode(2)
    cfg = tiny_spot_config()
    for i in range(20):
        e = _episode(3, i)
        occ, _, evs = episodes.slot_events(e)
        alive = int(occ.sum())
        for _, kind, _, _ in evs:
            alive += {"arrival": 1, "departure": -1}.get(kind, 0)
            assert 1 <= alive <= cfg["max_platforms"]
