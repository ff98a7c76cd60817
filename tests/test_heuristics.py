"""The vectorised Min-min scheduler against the loop it replaced."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import heuristics
from repro.core.problem import AllocationProblem


def min_min_loop(problem: AllocationProblem) -> np.ndarray:
    """The reference: Braun et al.'s Min-min as a plain O(tau^2) loop,
    ties to the lowest task, then the lowest platform."""
    mu, tau = problem.mu, problem.tau
    ready = np.zeros(mu)
    alloc = np.zeros((mu, tau))
    remaining = set(range(tau))
    while remaining:
        best = None
        for j in sorted(remaining):
            ect = ready + problem.beta_n[:, j] + problem.gamma[:, j]
            i = int(np.argmin(ect))
            if best is None or ect[i] < best[0]:
                best = (ect[i], i, j)
        _, i, j = best
        ready[i] += problem.beta_n[i, j] + problem.gamma[i, j]
        alloc[i, j] = 1.0
        remaining.remove(j)
    return alloc


def _problem(seed: int) -> AllocationProblem:
    r = np.random.default_rng(seed)
    mu, tau = int(r.integers(1, 7)), int(r.integers(1, 60))
    gamma = r.uniform(0.1, 5.0, (mu, tau)) * (r.random((mu, tau)) < 0.8)
    # repeated work sizes and rates make ties common
    return AllocationProblem(r.choice([1e-6, 2e-6, 5e-6], (mu, tau)), gamma,
                             r.choice([1e5, 1e6], tau),
                             r.uniform(60, 600, mu), r.uniform(0.01, 0.1, mu))


@pytest.mark.parametrize("seed", range(12))
def test_min_min_matches_the_loop(seed):
    p = _problem(seed)
    np.testing.assert_array_equal(heuristics.min_min(p), min_min_loop(p))


def test_min_min_places_every_task_once():
    p = _problem(99)
    alloc = heuristics.min_min(p)
    np.testing.assert_array_equal(alloc.sum(axis=0), 1.0)
    assert set(np.unique(alloc)) <= {0.0, 1.0}
