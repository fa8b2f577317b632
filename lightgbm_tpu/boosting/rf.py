"""Random forest mode.

Analog of the reference ``src/boosting/rf.hpp`` (``RF`` :25): no shrinkage,
bagging (or feature sampling) required, gradients computed ONCE from the
constant init score (no boosting), every tree carries the init-score bias
(AddBias), and the tracked score is the *running average* of tree outputs
(``MultiplyScore`` dance at rf.hpp:158-160) so metrics and prediction use
mean ensemble output (``average_output``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset
from ..objectives import Objective
from ..tree import Tree
from .gbdt import GBDT, kEpsilon

__all__ = ["RF"]


class RF(GBDT):
    average_output = True

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[Objective],
                 valid_sets: Sequence[Dataset] = (), **kwargs):
        if objective is None:
            raise ValueError("RF mode does not support custom objective "
                             "(rf.hpp Boosting check)")
        if config.data_sample_strategy == "bagging" and not (
                (config.bagging_freq > 0 and 0 < config.bagging_fraction < 1)
                or 0 < config.feature_fraction < 1):
            # rf.hpp Init: bagging strategy needs actual subsampling;
            # the goss strategy is accepted as-is (CHECK_EQ else-branch)
            raise ValueError(
                "RF needs bagging (bagging_freq > 0 and bagging_fraction "
                "< 1) or feature_fraction < 1 (rf.hpp Init check)")
        super().__init__(config, train_set, objective, valid_sets, **kwargs)
        self.shrinkage = 1.0
        if self.num_init_iteration > 0 and config.boost_from_average:
            # rf.hpp Boosting recomputes BoostFromAverage regardless of
            # num_init_iteration: continued-RF gradients are taken at the
            # label-average init score and new trees carry it as AddBias
            # (GBDT.__init__ zeroes _init_scores on the init_row_scores
            # path — that is the boosted-sum semantic, not RF's)
            self._init_scores = np.resize(np.asarray(
                self.objective.boost_from_score(),
                np.float64).reshape(-1), self.K)
        # constant gradients at the init score (rf.hpp Boosting): RF never
        # boosts, every tree fits the same residuals
        init = jnp.asarray(self._init_scores, jnp.float32)[:, None]
        tmp_scores = jnp.zeros_like(self.scores) + init
        if self.objective.num_model_per_iteration > 1:
            g, h = self.objective.get_gradients(
                tmp_scores.T, self.label_dev, self.weight_dev)
            self._g0, self._h0 = g.T, h.T
        else:
            g, h = self.objective.get_gradients(
                tmp_scores[0], self.label_dev, self.weight_dev)
            self._g0, self._h0 = g[None, :], h[None, :]
        # scores hold the running average of tree outputs, not a boosted
        # sum; start from zero (bias rides inside each tree). For continued
        # training the init_row_scores of an average_output base model are
        # already averages, so they stand as-is (rf.hpp Init MultiplyScore).
        if self.num_init_iteration == 0:
            self.scores = jnp.zeros_like(self.scores)
            self.valid_scores = [jnp.zeros_like(v)
                                 for v in self.valid_scores]

    def _grads(self, it: int):
        return self._g0, self._h0

    def train_one_iter(self, gradients=None, hessians=None, *,
                       defer: bool = False) -> bool:
        # defer accepted for interface parity, ignored (RF averages
        # scores with host-side iteration weights — eager loop only)
        if gradients is not None or hessians is not None:
            raise ValueError("RF mode does not support custom gradients")
        cfg = self.config
        g, h, count_mask = self._sampling(self.iter_, self._g0, self._h0)
        fmask = self._feature_mask()
        n = float(self.iter_ + self.num_init_iteration)
        for k in range(self.K):
            gh = jnp.stack([g[k], h[k], count_mask], axis=1)
            tree_arrays, row_leaf, valid_rls, _ = \
                self._build_one_tree(gh, fmask, k)
            host = jax.tree.map(np.asarray, tree_arrays)
            bias = float(self._init_scores[k])
            tree = Tree.from_device(host, self.train_set.bin_mappers,
                                    self.train_set.used_features, 1.0)
            grew = int(host.num_leaves) > 1
            # rf.hpp:148-176 — multi-leaf trees always carry the init bias
            # (AddBias); a no-split iteration stores the constant init tree
            # the FIRST time only, later no-split iterations store a zero
            # tree and leave the running average untouched
            add_bias = abs(bias) > kEpsilon and (grew or self.iter_ == 0)
            if add_bias:
                tree.leaf_value += bias
                tree.internal_value += bias
                tree_arrays = self._bias_adjust_device(tree_arrays, bias, 1.0)
            if grew or self.iter_ == 0:
                # running average with the global iteration count as
                # weight (rf.hpp:158-160 MultiplyScore(n) -> add ->
                # MultiplyScore(1/(n+1)))
                one = jnp.asarray(1.0, jnp.float32)
                new_tr = self._update_score_jit(
                    self.scores[k] * n, tree_arrays.leaf_values, row_leaf,
                    one)
                self.scores = self.scores.at[k].set(new_tr / (n + 1.0))
                for vi, vrl in enumerate(valid_rls):
                    new_va = self._update_score_jit(
                        self.valid_scores[vi][k] * n,
                        tree_arrays.leaf_values, vrl, one)
                    self.valid_scores[vi] = \
                        self.valid_scores[vi].at[k].set(new_va / (n + 1.0))
            self.models.append(tree)

        self.iter_ += 1
        return False  # RF never early-stops (rf.hpp TrainOneIter)

    def rollback_one_iter(self):
        """RF::RollbackOneIter (rf.hpp:184-203): scores are running
        AVERAGES, so undoing iteration n is Shrinkage(-1) +
        MultiplyScore(n) + AddScore + MultiplyScore(1/(n-1)), i.e.
        scores = (scores * n - tree_pred) / (n - 1) — NOT the boosted-sum
        subtraction GBDT does."""
        if self.iter_ <= 0:
            return
        n = float(self.iter_ + self.num_init_iteration)
        uf = self.train_set.used_features
        nan_bins = np.asarray(self.nan_bin_pf)
        bins_h = self._host_feature_bins(np.asarray(self.train_dd.bins))
        vbins_h = [self._host_feature_bins(np.asarray(dd.bins))
                   for dd in self.valid_dd]
        for k in range(self.K):
            tree = self.models[-(self.K - k)]
            pred = jnp.asarray(tree.predict_binned(bins_h, uf, nan_bins),
                               jnp.float32)
            if n > 1:
                new = (self.scores[k] * n - pred) / (n - 1.0)
            else:
                new = jnp.zeros_like(self.scores[k])
            self.scores = self.scores.at[k].set(new)
            for vi, vb in enumerate(vbins_h):
                vpred = jnp.asarray(tree.predict_binned(vb, uf, nan_bins),
                                    jnp.float32)
                if n > 1:
                    vnew = (self.valid_scores[vi][k] * n - vpred) / (n - 1.0)
                else:
                    vnew = jnp.zeros_like(self.valid_scores[vi][k])
                self.valid_scores[vi] = self.valid_scores[vi].at[k].set(vnew)
        for _ in range(self.K):
            self.models.pop()
        self.iter_ -= 1
