"""Bayesian-optimization strategy search (ref bayes_opt_sg.py:35).

The contract test: on the 8-device strategy space, BO must find the
known-best strategy while evaluating strictly fewer candidates than
exhaustive search would.
"""

import math

import numpy as np

from dlrover_tpu.accelerate.bayes_search import (
    BayesStrategySearch,
    encode_strategy,
)
from dlrover_tpu.accelerate.strategy import (
    Strategy,
    candidate_strategies,
)


def _space():
    """60-candidate space: 10 mesh factorizations x mb x remat."""
    return candidate_strategies(
        8,
        micro_batch_sizes=(4, 8, 16),
        remats=(True, False),
    )


def _true_throughput(s: Strategy) -> float:
    """Synthetic-but-structured objective, smooth in the encoding:
    peaked at fsdp=4/data=2, mb=8, remat off."""
    d = s.mesh_dict
    x = math.log2(max(d.get("fsdp", 1), 1))
    y = math.log2(max(d.get("tensor", 1), 1))
    mb = math.log2(s.micro_batch_size)
    score = 100.0 * math.exp(
        -((x - 2.0) ** 2) / 2 - (y**2) / 2 - ((mb - 3.0) ** 2) / 4
    )
    if s.remat:
        score *= 0.8
    return score


class TestBayesSearch:
    def test_finds_best_with_fewer_evals_than_exhaustive(self):
        cands = _space()
        true_best = max(cands, key=_true_throughput)
        # cost prior loosely anti-correlated with the objective, the
        # way the memory model is: it seeds, not decides.
        prior = [-_true_throughput(c) * 0.5 + i * 0.01
                 for i, c in enumerate(cands)]
        budget = len(cands) // 3
        search = BayesStrategySearch(cands, cost_prior=prior, seed=1)
        while search.should_continue(budget):
            c = search.suggest()
            search.observe(c, _true_throughput(c))
        assert search.evaluated_count() <= budget
        assert search.evaluated_count() < len(cands)
        best = search.best_strategy()
        assert _true_throughput(best) >= 0.95 * _true_throughput(
            true_best
        )

    def test_adversarial_prior_still_converges(self):
        """Even when the cost model seeds the WORST candidates first,
        the GP recovers within a modest budget."""
        cands = _space()
        true_best = max(cands, key=_true_throughput)
        prior = [_true_throughput(c) for c in cands]  # worst first
        search = BayesStrategySearch(cands, cost_prior=prior, seed=2)
        budget = len(cands) // 2
        while search.should_continue(budget):
            c = search.suggest()
            search.observe(c, _true_throughput(c))
        best = search.best_strategy()
        assert _true_throughput(best) >= 0.9 * _true_throughput(
            true_best
        )

    def test_failures_observed_as_avoided_points(self):
        cands = _space()
        search = BayesStrategySearch(cands, seed=3)
        # first two candidates fail (e.g. OOM)
        for _ in range(2):
            c = search.suggest()
            search.observe(c, None)
        assert search.best_strategy() is None
        c = search.suggest()
        search.observe(c, 10.0)
        assert search.best_strategy() == c
        assert search.best_throughput() == 10.0

    def test_never_suggests_evaluated_candidate(self):
        cands = _space()[:10]
        search = BayesStrategySearch(cands, seed=4)
        seen = []
        while search.should_continue(len(cands)):
            c = search.suggest()
            assert c not in seen
            seen.append(c)
            search.observe(c, float(len(seen)))
        assert len(seen) == len(cands)

    def test_encoding_distinguishes_strategies(self):
        cands = _space()
        encs = {tuple(encode_strategy(c)) for c in cands}
        assert len(encs) == len(cands)

    def test_gp_interpolates(self):
        from dlrover_tpu.accelerate.bayes_search import _GP

        rng = np.random.default_rng(0)
        X = rng.normal(size=(20, 3))
        y = (X**2).sum(1)
        gp = _GP(length_scale=1.0)
        gp.fit(X, y)
        mu, sigma = gp.predict(X)
        np.testing.assert_allclose(mu, y, atol=0.3)
        assert (sigma < 0.3).all()


class TestObserveDedupe:
    """Re-observed cached trials and duplicated candidate grids must
    not double-weight the GP, and suggest must never re-propose an
    already-evaluated point while untried candidates remain."""

    def _dup_space(self):
        cands = _space()[:6]
        # the same strategies again, at different indices
        return cands + list(cands[:3])

    def test_duplicate_candidates_collapse_to_one_observation(self):
        cands = self._dup_space()
        search = BayesStrategySearch(cands, seed=0)
        search.observe(cands[0], 5.0)
        search.observe(cands[6], 7.0)  # identical to cands[0]
        assert search.evaluated_count() == 1
        assert len(search._observed) == 1
        assert search.best_throughput() == 7.0  # latest wins

    def test_suggest_skips_duplicates_of_evaluated(self):
        cands = self._dup_space()
        search = BayesStrategySearch(cands, seed=1)
        seen = []
        while search.should_continue(len(cands)):
            c = search.suggest()
            assert c not in seen, "re-proposed an evaluated point"
            seen.append(c)
            search.observe(c, float(len(seen)))
        # every DISTINCT candidate evaluated exactly once
        assert len(seen) == 6

    def test_reobserve_success_clears_stale_failure(self):
        cands = _space()[:4]
        search = BayesStrategySearch(cands, seed=2)
        search.observe(cands[0], None)
        assert search.best_strategy() is None
        search.observe(cands[0], 3.0)  # a later real measurement
        assert search.best_strategy() == cands[0]


class TestWarmStart:
    def test_replays_only_known_candidates(self):
        cands = _space()[:8]
        outside = _space()[10]
        search = BayesStrategySearch(cands, seed=0)
        n = search.warm_start(
            [
                (cands[1], 5.0),
                (cands[2], None),  # cached OOM -> avoided point
                (outside, 99.0),  # not in this grid: skipped
            ]
        )
        assert n == 2
        assert search.evaluated_count() == 2
        assert search.best_strategy() == cands[1]
        # the cached failure is a zero point, not a winner
        assert search.best_throughput() == 5.0

    def test_warm_cache_reaches_same_best_with_fewer_evals(self):
        """The counting-evaluator contract: a search warm-started from
        a previous run's observations reaches the same best strategy
        with STRICTLY fewer fresh evaluations."""
        cands = _space()
        budget = len(cands) // 3

        def run(warm_obs):
            search = BayesStrategySearch(cands, seed=3)
            search.warm_start(warm_obs)
            evals = 0
            while search.should_continue(budget):
                c = search.suggest()
                search.observe(c, _true_throughput(c))
                evals += 1
            return search, evals

        cold, cold_evals = run([])
        warm_obs = [
            (cands[i], t) for i, t in cold._observed.items()
        ]
        warm, warm_evals = run(warm_obs)
        assert warm_evals < cold_evals
        assert warm_evals == 0  # fully warm: zero fresh dry-runs
        assert warm.best_strategy() == cold.best_strategy()

    def test_partial_warm_start_still_counts_against_budget(self):
        cands = _space()[:10]
        search = BayesStrategySearch(cands, seed=4)
        search.warm_start([(cands[0], 1.0), (cands[1], 2.0)])
        evals = 0
        while search.should_continue(5):
            c = search.suggest()
            assert c not in (cands[0], cands[1])
            search.observe(c, 0.5)
            evals += 1
        assert evals == 3  # budget 5 minus 2 cached
