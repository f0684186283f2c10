"""Bayesian-optimization strategy search.

Capability parity with the reference's BO search-graph algorithm
(atorch/auto/engine/sg_algo/bayes_opt_sg.py:35 ``BOAlgorithm``, backed
by the vendored HEBO library in sg_algo/hebo/) without vendoring a
framework: a small numpy Gaussian process (RBF kernel, Cholesky fit)
with expected-improvement acquisition over a feature encoding of the
strategy space (mesh-axis log-sizes x remat x microbatch x optimizer x
dtype).

Why BO here matters more than on GPU: a TPU dry-run is dominated by
XLA compile time (tens of seconds), so every avoided dry-run is real
wall clock. The search is seeded by the analyser's memory cost model
(the candidates most likely to both fit and run fast get evaluated
first), and failed candidates (OOM, bad shapes) are observed as
zero-throughput points so the GP steers away from their neighborhood.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from dlrover_tpu.accelerate.strategy import Strategy
from dlrover_tpu.common.log import get_logger

logger = get_logger("bayes_search")

_AXES = ("data", "fsdp", "tensor", "seq", "pipe", "expert")
_OPTIMIZERS = ("adamw", "agd", "adam8bit", "adam4bit", "sgd")
_DTYPES = ("bfloat16", "float32")


def encode_strategy(s: Strategy) -> np.ndarray:
    """Feature vector: log2 axis sizes, remat flag, log2 microbatch,
    optimizer/dtype one-hots. Smooth-ish coordinates so nearby configs
    (e.g. fsdp=2 vs fsdp=4) have correlated throughput under the RBF
    kernel."""
    from dlrover_tpu.accelerate.remat import POLICY_NAMES, canonical

    d = s.mesh_dict
    feats = [math.log2(max(d.get(a, 1), 1)) for a in _AXES]
    # one-hot over named remat policies ("none" must not look like
    # "full" to the GP)
    remat = canonical(s.remat)
    feats.extend(1.0 if remat == n else 0.0 for n in POLICY_NAMES)
    feats.append(math.log2(max(s.micro_batch_size, 1)))
    feats.extend(
        1.0 if s.optimizer == o else 0.0 for o in _OPTIMIZERS
    )
    feats.extend(1.0 if s.dtype == t else 0.0 for t in _DTYPES)
    return np.asarray(feats, np.float64)


class _GP:
    """Minimal exact GP: RBF kernel, unit signal variance on
    standardized targets, jittered Cholesky."""

    def __init__(self, length_scale: float = 1.0,
                 noise: float = 1e-3):
        self.ls = length_scale
        self.noise = noise
        self._X: Optional[np.ndarray] = None

    def _k(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        d2 = (
            (a**2).sum(1)[:, None]
            + (b**2).sum(1)[None, :]
            - 2.0 * a @ b.T
        )
        return np.exp(-0.5 * np.maximum(d2, 0.0) / self.ls**2)

    def fit(self, X: np.ndarray, y: np.ndarray) -> None:
        self._X = X
        self._y_mean = float(y.mean())
        self._y_std = float(y.std()) or 1.0
        yn = (y - self._y_mean) / self._y_std
        K = self._k(X, X) + self.noise * np.eye(len(X))
        self._L = np.linalg.cholesky(K)
        self._alpha = np.linalg.solve(
            self._L.T, np.linalg.solve(self._L, yn)
        )

    def predict(self, Xs: np.ndarray):
        Ks = self._k(self._X, Xs)
        mu = Ks.T @ self._alpha
        v = np.linalg.solve(self._L, Ks)
        var = np.maximum(1.0 - (v**2).sum(0), 1e-12)
        return (
            mu * self._y_std + self._y_mean,
            np.sqrt(var) * self._y_std,
        )


def _norm_cdf(z: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.vectorize(math.erf)(z / math.sqrt(2.0)))


def _norm_pdf(z: np.ndarray) -> np.ndarray:
    return np.exp(-0.5 * z**2) / math.sqrt(2.0 * math.pi)


class BayesStrategySearch:
    """Sequential BO over a finite candidate set.

    ``cost_prior``: lower-is-better scores from the analyser's memory
    model — the first ``n_init`` evaluations walk this ranking (the
    reference seeds HEBO the same way with its resource prefilter).

    Usage::

        search = BayesStrategySearch(candidates, cost_prior)
        while search.should_continue(budget):
            cand = search.suggest()
            search.observe(cand, throughput_or_None)
        best = search.best_strategy()
    """

    def __init__(
        self,
        candidates: Sequence[Strategy],
        cost_prior: Optional[Sequence[float]] = None,
        n_init: int = 2,
        xi: float = 0.01,
        seed: int = 0,
    ):
        if not candidates:
            raise ValueError("empty candidate set")
        self.candidates = list(candidates)
        # Canonical index per candidate: identical strategies (callers
        # can hand in duplicated grids, and cached trials re-observe
        # points) must collapse to ONE GP observation — a duplicated
        # point silently double-weights its neighborhood — and suggest
        # must never re-propose an evaluated point via its twin.
        first_idx: Dict[Strategy, int] = {}
        self._canon: List[int] = []
        for i, c in enumerate(self.candidates):
            self._canon.append(first_idx.setdefault(c, i))
        self._n_distinct = len(first_idx)
        self._X = np.stack(
            [encode_strategy(c) for c in self.candidates]
        )
        # standardize features so one RBF length scale fits all dims
        self._feat_mean = self._X.mean(0)
        self._feat_std = self._X.std(0)
        self._feat_std[self._feat_std == 0] = 1.0
        self._X = (self._X - self._feat_mean) / self._feat_std
        if cost_prior is not None:
            order = list(np.argsort(np.asarray(cost_prior)))
        else:
            order = list(range(len(self.candidates)))
        self._seed_order = order
        self.n_init = min(n_init, len(self.candidates))
        self.xi = xi
        self._rng = np.random.default_rng(seed)
        self._observed: Dict[int, float] = {}
        self._failed: set = set()
        self._gp = _GP(length_scale=1.0)

    # -- loop ------------------------------------------------------------

    def evaluated_count(self) -> int:
        return len(self._observed)

    def should_continue(self, budget: int) -> bool:
        return (
            self.evaluated_count() < budget
            and self.evaluated_count() < self._n_distinct
        )

    def suggest(self) -> Strategy:
        """Next candidate: cost-model seeds first, then max expected
        improvement under the GP. Never re-proposes an evaluated point
        (or a duplicate of one) while untried candidates remain."""
        remaining = [
            i
            for i in range(len(self.candidates))
            if self._canon[i] == i and i not in self._observed
        ]
        if not remaining:
            raise RuntimeError("all candidates evaluated")
        if self.evaluated_count() < self.n_init:
            for i in self._seed_order:
                if self._canon[i] in self._observed:
                    continue
                return self.candidates[i]
        X_obs = self._X[list(self._observed)]
        y_obs = np.asarray(list(self._observed.values()))
        if np.allclose(y_obs, y_obs[0]):
            # degenerate GP (all failures so far): fall back to prior
            for i in self._seed_order:
                if self._canon[i] not in self._observed:
                    return self.candidates[i]
        self._gp.fit(X_obs, y_obs)
        mu, sigma = self._gp.predict(self._X[remaining])
        best = y_obs.max()
        z = (mu - best - self.xi_abs(best)) / sigma
        ei = (mu - best - self.xi_abs(best)) * _norm_cdf(
            z
        ) + sigma * _norm_pdf(z)
        pick = remaining[int(np.argmax(ei))]
        return self.candidates[pick]

    def xi_abs(self, best: float) -> float:
        return self.xi * abs(best)

    def observe(
        self, strategy: Strategy, throughput: Optional[float]
    ) -> None:
        """``throughput=None`` marks a failed dry-run (OOM etc.): the
        point is kept as zero so the GP avoids its neighborhood.

        Deduped: re-observing an identical strategy (a replayed cached
        trial, a duplicated candidate) updates the ONE point for it —
        the GP never sees the same coordinates twice. A fresh success
        clears a stale failure mark for the point (latest wins)."""
        idx = self._canon[self.candidates.index(strategy)]
        if throughput is None:
            self._failed.add(idx)
            throughput = 0.0
        else:
            self._failed.discard(idx)
        self._observed[idx] = float(throughput)

    def warm_start(
        self,
        observations,
    ) -> int:
        """Replay cached trials (``accelerate/tune_cache.py``) into the
        search before any dry-run is spent: an iterable of
        ``(strategy, throughput_or_None)`` pairs. Pairs whose strategy
        is not in this search's candidate set are skipped (the cache
        may hold points outside the currently-viable grid). Replayed
        points count as evaluated — ``should_continue`` budgets and
        ``suggest`` both see them — so a warm cache directly converts
        into fewer dry-runs. Returns the number replayed."""
        known = set(self.candidates)
        n = 0
        for strategy, throughput in observations:
            if strategy not in known:
                continue
            self.observe(strategy, throughput)
            n += 1
        if n:
            logger.info(
                "warm start: replayed %d cached trial(s); "
                "%d distinct candidates remain unevaluated",
                n,
                self._n_distinct - self.evaluated_count(),
            )
        return n

    def best_strategy(self) -> Optional[Strategy]:
        ok = {
            i: t
            for i, t in self._observed.items()
            if i not in self._failed
        }
        if not ok:
            return None
        return self.candidates[max(ok, key=ok.get)]

    def best_throughput(self) -> Optional[float]:
        ok = [
            t
            for i, t in self._observed.items()
            if i not in self._failed
        ]
        return max(ok) if ok else None
