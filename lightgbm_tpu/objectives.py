"""Objective functions: per-row gradients/hessians on device.

TPU-native analog of the reference objective layer
(reference: src/objective/*.hpp, abstract interface
include/LightGBM/objective_function.h: GetGradients(:37), BoostFromScore(:51),
ConvertOutput(:67), NumModelPerIteration(:57), RenewTreeOutput(:46)).
The reference's per-row OpenMP loops become vectorized jnp expressions;
weights are folded into grad/hess exactly as the reference does.

Formulas are carried over 1:1 with file:line citations on each class.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .utils import log

K_EPSILON = 1e-15


def _percentile(data: np.ndarray, alpha: float) -> float:
    """reference: regression_objective.hpp:17-47 PercentileFun (unweighted)."""
    cnt = len(data)
    if cnt <= 1:
        return float(data[0]) if cnt else 0.0
    d = np.sort(data)[::-1]  # descending; pos counts from the top
    float_pos = (1.0 - alpha) * cnt
    pos = int(float_pos)
    if pos < 1:
        return float(d[0])
    if pos >= cnt:
        return float(d[-1])
    bias = float_pos - pos
    v1, v2 = float(d[pos - 1]), float(d[pos])
    return v1 - (v1 - v2) * bias


def _weighted_percentile(data: np.ndarray, weight: np.ndarray, alpha: float) -> float:
    """reference: regression_objective.hpp:49-87 WeightedPercentileFun."""
    cnt = len(data)
    if cnt <= 1:
        return float(data[0]) if cnt else 0.0
    order = np.argsort(data, kind="stable")
    d = data[order]
    cdf = np.cumsum(weight[order])
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, cnt - 1)
    if pos == 0 or pos == cnt - 1:
        return float(d[pos])
    v1, v2 = float(d[pos - 1]), float(d[pos])
    if pos + 1 < cnt and cdf[pos + 1] - cdf[pos] >= 1.0:
        return (threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos]) * (v2 - v1) + v1
    return v2


class ObjectiveFunction:
    """Base objective (reference: include/LightGBM/objective_function.h)."""

    name = "base"
    num_model_per_iteration = 1
    is_constant_hessian = False
    need_renew_tree_output = False
    # False when get_grad_hess has host-side state (e.g. a numpy RNG draw)
    # that would freeze at trace time inside a jitted training step — such
    # objectives must run the phase-by-phase path (gbdt._fused_ok)
    jit_safe_gradients = True

    def __init__(self, config: Config):
        self.config = config

    def init(self, label: np.ndarray, weight: Optional[np.ndarray],
             groups: Optional[np.ndarray] = None) -> None:
        self.label_np = np.asarray(label, dtype=np.float64)
        self.weight_np = (np.asarray(weight, dtype=np.float64)
                         if weight is not None else None)
        self.num_data = len(self.label_np)
        self.label = jnp.asarray(self.label_np, dtype=jnp.float32)
        self.weight = (jnp.asarray(self.weight_np, dtype=jnp.float32)
                       if weight is not None else None)

    def _apply_weight(self, grad, hess):
        if self.weight is not None:
            return grad * self.weight, hess * self.weight
        return grad, hess

    def get_grad_hess(self, score: jax.Array):
        raise NotImplementedError

    # ------------------------------------------------- traced-program use
    def device_consts(self) -> dict:
        """Every device-resident array this objective closes over in
        ``get_grad_hess`` (label, weight, and subclass derivatives such as
        the binary label_sign/label_weight or the multiclass onehot).

        A jitted training step that calls ``get_grad_hess`` directly
        embeds these O(N) arrays as CONSTANTS of the compiled program —
        and every label-derived subexpression (``label_sign * sigmoid``,
        the softmax onehot subtraction setup, ...) becomes dataset-
        constant compute XLA constant-folds AT COMPILE TIME, taking
        multi-second alarms per instruction at 10M-row scale. The
        fused step instead fetches this dict once,
        passes it as program OPERANDS, and traces ``get_grad_hess``
        under :meth:`bound` so the arrays enter the program as
        parameters that cannot be folded."""
        return {k: v for k, v in vars(self).items()
                if isinstance(v, jax.Array)}

    def bound(self, consts: dict):
        """Context manager substituting ``device_consts``-shaped values
        (typically tracers, inside a jit trace) for the objective's
        device arrays, restoring the originals on exit."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            old = {k: getattr(self, k) for k in consts}
            try:
                for k, v in consts.items():
                    setattr(self, k, v)
                yield self
            finally:
                for k, v in old.items():
                    setattr(self, k, v)
        return _ctx()

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, raw: jax.Array) -> jax.Array:
        return raw

    def renew_tree_output(self, pred_leaf: np.ndarray, score: np.ndarray,
                          num_leaves: int) -> Optional[np.ndarray]:
        """Per-leaf output refresh for L1-family objectives
        (reference: objective_function.h:46 RenewTreeOutput;
        regression_objective.hpp:253-263, 537-548, 640-652). Returns new leaf
        values [num_leaves] or None."""
        return None


# ------------------------------------------------------------- regression
class RegressionL2(ObjectiveFunction):
    """reference: regression_objective.hpp:93-201 (RegressionL2loss)."""
    name = "regression"
    is_constant_hessian = True

    def init(self, label, weight, groups=None):
        if self.config.reg_sqrt:
            label = np.sign(label) * np.sqrt(np.abs(label))
        super().init(label, weight, groups)

    def get_grad_hess(self, score):
        return self._apply_weight(score - self.label, jnp.ones_like(score))

    def boost_from_score(self, class_id: int = 0) -> float:
        # reference: regression_objective.hpp:173-198 (weighted mean label)
        if self.weight_np is not None:
            return float(np.sum(self.label_np * self.weight_np) / np.sum(self.weight_np))
        return float(np.mean(self.label_np))

    def convert_output(self, raw):
        if self.config.reg_sqrt:
            return jnp.sign(raw) * raw * raw
        return raw


class RegressionL1(RegressionL2):
    """reference: regression_objective.hpp:207-290 (RegressionL1loss)."""
    name = "regression_l1"
    need_renew_tree_output = True

    def get_grad_hess(self, score):
        diff = score - self.label
        if self.weight is not None:
            return jnp.sign(diff) * self.weight, self.weight
        return jnp.sign(diff), jnp.ones_like(score)

    def boost_from_score(self, class_id: int = 0) -> float:
        if self.weight_np is not None:
            return _weighted_percentile(self.label_np, self.weight_np, 0.5)
        return _percentile(self.label_np, 0.5)

    def _renew_alpha(self) -> float:
        return 0.5

    def renew_tree_output(self, pred_leaf, score, num_leaves):
        # reference: regression_objective.hpp:253-263 — leaf value := percentile
        # of (label - score) over the leaf's rows
        residual = self.label_np - score
        alpha = self._renew_alpha()
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            mask = pred_leaf == leaf
            if not mask.any():
                continue
            r = residual[mask]
            if self.weight_np is not None:
                out[leaf] = _weighted_percentile(r, self.weight_np[mask], alpha)
            else:
                out[leaf] = _percentile(r, alpha)
        return out


class RegressionHuber(RegressionL2):
    """reference: regression_objective.hpp:293-348 (RegressionHuberLoss)."""
    name = "huber"

    def get_grad_hess(self, score):
        diff = score - self.label
        alpha = self.config.alpha
        g = jnp.where(jnp.abs(diff) <= alpha, diff, jnp.sign(diff) * alpha)
        return self._apply_weight(g, jnp.ones_like(score))


class RegressionFair(RegressionL2):
    """reference: regression_objective.hpp:351-395 (RegressionFairLoss)."""
    name = "fair"

    def get_grad_hess(self, score):
        c = self.config.fair_c
        x = score - self.label
        g = c * x / (jnp.abs(x) + c)
        h = c * c / ((jnp.abs(x) + c) ** 2)
        return self._apply_weight(g, h)


class RegressionPoisson(RegressionL2):
    """reference: regression_objective.hpp:398-477 (RegressionPoissonLoss).
    Score is log-mean: grad = exp(s) - y, hess = exp(s + poisson_max_delta_step)."""
    name = "poisson"

    def init(self, label, weight, groups=None):
        if np.any(np.asarray(label) < 0):
            log.fatal("[poisson]: at least one target label is negative")
        super().init(label, weight, groups)

    def get_grad_hess(self, score):
        g = jnp.exp(score) - self.label
        h = jnp.exp(score + self.config.poisson_max_delta_step)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id: int = 0) -> float:
        mean = RegressionL2.boost_from_score(self, class_id)
        return float(np.log(max(mean, 1e-300)))

    def convert_output(self, raw):
        return jnp.exp(raw)


class RegressionQuantile(RegressionL2):
    """reference: regression_objective.hpp:478-573 (RegressionQuantileloss)."""
    name = "quantile"
    is_constant_hessian = True
    need_renew_tree_output = True

    def get_grad_hess(self, score):
        alpha = self.config.alpha
        delta = score - self.label
        g = jnp.where(delta >= 0, 1.0 - alpha, -alpha)
        return self._apply_weight(g, jnp.ones_like(score))

    def boost_from_score(self, class_id: int = 0) -> float:
        if self.weight_np is not None:
            return _weighted_percentile(self.label_np, self.weight_np, self.config.alpha)
        return _percentile(self.label_np, self.config.alpha)

    def _renew_alpha(self) -> float:
        return self.config.alpha

    renew_tree_output = RegressionL1.renew_tree_output


class RegressionMAPE(RegressionL1):
    """reference: regression_objective.hpp:576-672 (RegressionMAPELOSS)."""
    name = "mape"

    def init(self, label, weight, groups=None):
        super().init(label, weight, groups)
        lw = 1.0 / np.maximum(1.0, np.abs(self.label_np))
        if self.weight_np is not None:
            lw = lw * self.weight_np
        self.label_weight_np = lw
        self.label_weight = jnp.asarray(lw, dtype=jnp.float32)

    def get_grad_hess(self, score):
        diff = score - self.label
        g = jnp.sign(diff) * self.label_weight
        h = self.weight if self.weight is not None else jnp.ones_like(score)
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        return _weighted_percentile(self.label_np, self.label_weight_np, 0.5)

    def renew_tree_output(self, pred_leaf, score, num_leaves):
        # reference: regression_objective.hpp:640-652 — weighted median of
        # residual with label_weight_
        residual = self.label_np - score
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            mask = pred_leaf == leaf
            if mask.any():
                out[leaf] = _weighted_percentile(residual[mask],
                                                 self.label_weight_np[mask], 0.5)
        return out


class RegressionGamma(RegressionPoisson):
    """reference: regression_objective.hpp:677-707 (RegressionGammaLoss)."""
    name = "gamma"

    def get_grad_hess(self, score):
        g = 1.0 - self.label * jnp.exp(-score)
        h = self.label * jnp.exp(-score)
        return self._apply_weight(g, h)


class RegressionTweedie(RegressionPoisson):
    """reference: regression_objective.hpp:712-751 (RegressionTweedieLoss)."""
    name = "tweedie"

    def get_grad_hess(self, score):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        g = -self.label * e1 + e2
        h = -self.label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return self._apply_weight(g, h)


# ----------------------------------------------------------------- binary
class BinaryLogloss(ObjectiveFunction):
    """reference: src/objective/binary_objective.hpp:21-199."""
    name = "binary"

    def __init__(self, config: Config, is_pos=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            log.fatal(f"Sigmoid parameter {self.sigmoid} should be greater than zero")
        self._is_pos = is_pos if is_pos is not None else (lambda y: y > 0)

    def init(self, label, weight, groups=None):
        super().init(label, weight, groups)
        is_pos = self._is_pos(self.label_np)
        cnt_pos = int(np.sum(is_pos))
        cnt_neg = self.num_data - cnt_pos
        self.need_train = not (cnt_pos == 0 or cnt_neg == 0)
        if not self.need_train:
            log.warning("Contains only one class")
        # label weights (binary_objective.hpp:88-102)
        w_pos, w_neg = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.config.scale_pos_weight
        self._is_pos_np = is_pos
        self.label_sign = jnp.asarray(np.where(is_pos, 1.0, -1.0), dtype=jnp.float32)
        self.label_weight = jnp.asarray(np.where(is_pos, w_pos, w_neg), dtype=jnp.float32)
        log.info(f"Number of positive: {cnt_pos}, number of negative: {cnt_neg}")

    def get_grad_hess(self, score):
        # reference: binary_objective.hpp:110-136
        if not self.need_train:
            return jnp.zeros_like(score), jnp.zeros_like(score)
        response = -self.label_sign * self.sigmoid / (
            1.0 + jnp.exp(self.label_sign * self.sigmoid * score))
        abs_response = jnp.abs(response)
        g = response * self.label_weight
        h = abs_response * (self.sigmoid - abs_response) * self.label_weight
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id: int = 0) -> float:
        # reference: binary_objective.hpp:139-161
        if self.weight_np is not None:
            pavg = float(np.sum(self._is_pos_np * self.weight_np) / np.sum(self.weight_np))
        else:
            pavg = float(np.mean(self._is_pos_np))
        pavg = min(max(pavg, K_EPSILON), 1.0 - K_EPSILON)
        initscore = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        log.info(f"[binary:BoostFromScore]: pavg={pavg:.6f} -> initscore={initscore:.6f}")
        return initscore

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))


# -------------------------------------------------------------- multiclass
class MulticlassSoftmax(ObjectiveFunction):
    """reference: src/objective/multiclass_objective.hpp:20-180."""
    name = "multiclass"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_model_per_iteration = self.num_class
        self.factor = self.num_class / (self.num_class - 1.0)

    def init(self, label, weight, groups=None):
        super().init(label, weight, groups)
        li = self.label_np.astype(np.int32)
        if np.any((li < 0) | (li >= self.num_class)):
            log.fatal("Label must be in [0, num_class)")
        self.label_int = jnp.asarray(li)
        self.onehot = jax.nn.one_hot(self.label_int, self.num_class, dtype=jnp.float32)
        # class_init_probs_: weighted class frequencies
        w = self.weight_np if self.weight_np is not None else np.ones(self.num_data)
        probs = np.zeros(self.num_class)
        for k in range(self.num_class):
            probs[k] = np.sum(w * (li == k)) / np.sum(w)
        self.class_init_probs = probs

    def get_grad_hess(self, score):
        # score: [N, K]; reference: multiclass_objective.hpp:90-127
        p = jax.nn.softmax(score, axis=1)
        g = p - self.onehot
        h = self.factor * p * (1.0 - p)
        if self.weight is not None:
            g = g * self.weight[:, None]
            h = h * self.weight[:, None]
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        # reference: multiclass_objective.hpp:154-156
        return float(np.log(max(K_EPSILON, self.class_init_probs[class_id])))

    def convert_output(self, raw):
        return jax.nn.softmax(raw, axis=-1)


class MulticlassOVA(ObjectiveFunction):
    """reference: multiclass_objective.hpp:184-280 (one-vs-all binary)."""
    name = "multiclassova"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_model_per_iteration = self.num_class
        self.binaries = [BinaryLogloss(config, is_pos=(lambda y, k=k: y.astype(np.int32) == k))
                         for k in range(self.num_class)]

    def init(self, label, weight, groups=None):
        super().init(label, weight, groups)
        for b in self.binaries:
            b.init(label, weight, groups)

    def get_grad_hess(self, score):
        gs, hs = [], []
        for k, b in enumerate(self.binaries):
            g, h = b.get_grad_hess(score[:, k])
            gs.append(g)
            hs.append(h)
        return jnp.stack(gs, axis=1), jnp.stack(hs, axis=1)

    def boost_from_score(self, class_id: int = 0) -> float:
        return self.binaries[class_id].boost_from_score(0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))


# ------------------------------------------------------------ cross-entropy
class CrossEntropy(ObjectiveFunction):
    """reference: src/objective/xentropy_objective.hpp:44-147 (labels in [0,1])."""
    name = "cross_entropy"

    def init(self, label, weight, groups=None):
        if np.any((np.asarray(label) < 0) | (np.asarray(label) > 1)):
            log.fatal("[cross_entropy]: labels must be in [0, 1]")
        super().init(label, weight, groups)

    def get_grad_hess(self, score):
        z = 1.0 / (1.0 + jnp.exp(-score))
        g = z - self.label
        h = z * (1.0 - z)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id: int = 0) -> float:
        w = self.weight_np if self.weight_np is not None else np.ones(self.num_data)
        pavg = float(np.sum(self.label_np * w) / np.sum(w))
        pavg = min(max(pavg, K_EPSILON), 1.0 - K_EPSILON)
        return float(np.log(pavg / (1.0 - pavg)))

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-raw))


class CrossEntropyLambda(CrossEntropy):
    """reference: xentropy_objective.hpp:152-260 (weighted 'lambda' variant).
    Unweighted it reduces to plain cross-entropy (:195-197); the weighted form
    uses z = 1 - exp(-w*log1p(exp(s)))."""
    name = "cross_entropy_lambda"

    def get_grad_hess(self, score):
        if self.weight is None:
            z = 1.0 / (1.0 + jnp.exp(-score))
            return z - self.label, z * (1.0 - z)
        w = self.weight
        y = self.label
        enf = jnp.exp(-score)
        hhat = jnp.log1p(jnp.exp(score))
        z = 1.0 - jnp.exp(-w * hhat)
        g = (1.0 - y / jnp.maximum(z, K_EPSILON)) * w / (1.0 + enf)
        c = 1.0 / (1.0 - jnp.maximum(z, K_EPSILON))
        d = 1.0 + jnp.exp(score)
        a = w * jnp.exp(score) / (d * d)
        b = (c - 1.0) * w / d - c + 1.0
        h = a * (1.0 + y * b)
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        w = self.weight_np if self.weight_np is not None else np.ones(self.num_data)
        havg = float(np.sum(self.label_np * w) / np.sum(w))
        havg = max(havg, K_EPSILON)
        return float(np.log(np.expm1(havg))) if havg > K_EPSILON else float(np.log(K_EPSILON))

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))


_REGISTRY = {}
for _cls in [RegressionL2, RegressionL1, RegressionHuber, RegressionFair,
             RegressionPoisson, RegressionQuantile, RegressionMAPE,
             RegressionGamma, RegressionTweedie, BinaryLogloss,
             MulticlassSoftmax, MulticlassOVA, CrossEntropy, CrossEntropyLambda]:
    _REGISTRY[_cls.name] = _cls


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """reference: src/objective/objective_function.cpp CreateObjectiveFunction."""
    name = config.objective
    if name in ("none", "null", "custom", "na"):
        return None
    if name in ("lambdarank", "rank_xendcg"):
        from .ranking import create_ranking_objective
        return create_ranking_objective(config)
    if name not in _REGISTRY:
        log.fatal(f"Unknown objective: {name}")
    return _REGISTRY[name](config)
