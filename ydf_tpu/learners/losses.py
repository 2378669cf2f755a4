"""GBT losses: initial predictions, gradients/hessians, loss values.

Re-design of the reference's pluggable loss interface
(`ydf/learner/gradient_boosted_trees/loss/loss_interface.h:213-351`
AbstractLoss: InitialPredictions / UpdateGradients / Loss) as pure JAX
functions over batched prediction arrays. Implemented losses and their
reference counterparts:

  * BinomialLogLikelihood  — loss_imp_binomial.cc  (binary classification)
  * MeanSquaredError       — loss_imp_mean_square_error.cc (regression;
                             reported loss is RMSE, as in the reference)
  * MultinomialLogLikelihood — loss_imp_multinomial.cc (multiclass)
  * PoissonLoss            — loss_imp_poisson.cc (count regression, log link)
  * MeanAverageError       — loss_imp_mean_average_error.cc (median init)
  * BinaryFocalLoss        — loss_imp_binary_focal.cc (gradients/hessians
                             by JAX autodiff of the per-example focal term)

Conventions: predictions are raw scores [n, K] (K = num_trees_per_iter:
1 for binary/regression, C for multiclass). Gradients are d loss/d score, so
leaf Newton steps are -Σg/(Σh+λ) (the grower's HessianGainRule).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class BinomialLogLikelihood:
    """Binary cross-entropy on logits. labels int {0,1}."""

    name = "BINOMIAL_LOG_LIKELIHOOD"
    num_dims = 1

    def initial_predictions(self, labels, weights):
        # log-odds of the positive class (reference loss_imp_binomial.cc
        # InitialPredictions).
        p = jnp.sum(weights * labels) / (jnp.sum(weights) + _EPS)
        p = jnp.clip(p, _EPS, 1.0 - _EPS)
        return jnp.log(p / (1.0 - p))[None]

    def grad_hess(self, labels, preds):
        p = jax.nn.sigmoid(preds[:, 0])
        y = labels.astype(jnp.float32)
        g = p - y
        h = p * (1.0 - p)
        return g[:, None], h[:, None]

    def loss(self, labels, preds, weights, tag: str = "train"):
        # Reported as binomial deviance = 2 × weighted logloss, matching the
        # reference's displayed training loss.
        y = labels.astype(jnp.float32)
        ll = jax.nn.softplus(preds[:, 0]) - y * preds[:, 0]
        return 2.0 * jnp.sum(weights * ll) / (jnp.sum(weights) + _EPS)

    def predict_proba(self, preds):
        p1 = jax.nn.sigmoid(preds[:, 0])
        return jnp.stack([1.0 - p1, p1], axis=1)


@dataclasses.dataclass(frozen=True)
class MeanSquaredError:
    """Squared error; reported loss is RMSE (reference convention)."""

    name = "SQUARED_ERROR"
    num_dims = 1
    # grad_hess returns a hessian of ones, whatever the predictions: the
    # stats row's `h w` is then `w` bit for bit, and the histogram reads
    # that column instead of summing it (gbt.py:_hist_stat_columns).
    unit_hessian = True

    def initial_predictions(self, labels, weights):
        return (jnp.sum(weights * labels) / (jnp.sum(weights) + _EPS))[None]

    def grad_hess(self, labels, preds):
        g = preds[:, 0] - labels
        h = jnp.ones_like(g)
        return g[:, None], h[:, None]

    def loss(self, labels, preds, weights, tag: str = "train"):
        se = jnp.square(preds[:, 0] - labels)
        return jnp.sqrt(jnp.sum(weights * se) / (jnp.sum(weights) + _EPS))

    def predict_proba(self, preds):
        return preds


@dataclasses.dataclass(frozen=True)
class MultinomialLogLikelihood:
    """Softmax cross-entropy; one tree per class per iteration."""

    num_classes: int
    name = "MULTINOMIAL_LOG_LIKELIHOOD"

    @property
    def num_dims(self):
        return self.num_classes

    def initial_predictions(self, labels, weights):
        # Reference initializes multinomial at zero (loss_imp_multinomial.cc).
        return jnp.zeros((self.num_classes,), jnp.float32)

    def grad_hess(self, labels, preds):
        p = jax.nn.softmax(preds, axis=1)
        y = jax.nn.one_hot(labels, self.num_classes, dtype=jnp.float32)
        g = p - y
        h = p * (1.0 - p)
        return g, h

    def loss(self, labels, preds, weights, tag: str = "train"):
        logp = jax.nn.log_softmax(preds, axis=1)
        nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)[:, 0]
        return jnp.sum(weights * nll) / (jnp.sum(weights) + _EPS)

    def predict_proba(self, preds):
        return jax.nn.softmax(preds, axis=1)


@dataclasses.dataclass(frozen=True)
class PoissonLoss:
    """Poisson deviance on log-rate scores; labels are counts >= 0."""

    name = "POISSON"
    num_dims = 1

    def initial_predictions(self, labels, weights):
        mean = jnp.sum(weights * labels) / (jnp.sum(weights) + _EPS)
        return jnp.log(jnp.maximum(mean, _EPS))[None]

    def grad_hess(self, labels, preds):
        mu = jnp.exp(preds[:, 0])
        g = mu - labels
        return g[:, None], mu[:, None]

    def loss(self, labels, preds, weights, tag: str = "train"):
        # 2·(μ − y·log μ) + const: the Poisson deviance the reference
        # reports (loss_imp_poisson.cc).
        t = jnp.exp(preds[:, 0]) - labels * preds[:, 0]
        return 2.0 * jnp.sum(weights * t) / (jnp.sum(weights) + _EPS)

    def predict_proba(self, preds):
        return jnp.exp(preds)


@dataclasses.dataclass(frozen=True)
class MeanAverageError:
    """L1 regression: sign gradients, unit hessians, median init
    (reference loss_imp_mean_average_error.cc)."""

    name = "MEAN_AVERAGE_ERROR"
    num_dims = 1

    def initial_predictions(self, labels, weights):
        # Weighted median (reference loss_imp_mean_average_error.cc):
        # smallest label where the cumulative weight reaches half the total.
        order = jnp.argsort(labels)
        cw = jnp.cumsum(weights[order])
        idx = jnp.searchsorted(cw, 0.5 * cw[-1])
        return labels[order][jnp.minimum(idx, labels.shape[0] - 1)][None]

    def grad_hess(self, labels, preds):
        g = jnp.sign(preds[:, 0] - labels)
        return g[:, None], jnp.ones_like(g)[:, None]

    def loss(self, labels, preds, weights, tag: str = "train"):
        ae = jnp.abs(preds[:, 0] - labels)
        return jnp.sum(weights * ae) / (jnp.sum(weights) + _EPS)

    def predict_proba(self, preds):
        return preds


@dataclasses.dataclass(frozen=True)
class BinaryFocalLoss:
    """Focal loss (Lin et al. 2017) on logits; gamma focuses training on
    hard examples. Gradients/hessians by autodiff — no hand-derived
    formulas to get wrong (the reference hand-derives them in
    loss_imp_binary_focal.cc; the math is identical)."""

    gamma: float = 2.0
    alpha: float = 0.5
    name = "BINARY_FOCAL_LOSS"
    num_dims = 1

    def _example_loss(self, s, y):
        p = jax.nn.sigmoid(s)
        pt = jnp.where(y > 0.5, p, 1.0 - p)
        at = jnp.where(y > 0.5, self.alpha, 1.0 - self.alpha)
        return -at * (1.0 - pt) ** self.gamma * jnp.log(jnp.maximum(pt, _EPS))

    def initial_predictions(self, labels, weights):
        p = jnp.sum(weights * labels) / (jnp.sum(weights) + _EPS)
        p = jnp.clip(p, _EPS, 1.0 - _EPS)
        return jnp.log(p / (1.0 - p))[None]

    def grad_hess(self, labels, preds):
        y = labels.astype(jnp.float32)
        s = preds[:, 0]
        g = jax.vmap(jax.grad(self._example_loss))(s, y)
        h = jax.vmap(jax.grad(jax.grad(self._example_loss)))(s, y)
        # Newton steps need positive curvature; clamp like the reference.
        return g[:, None], jnp.maximum(h, _EPS)[:, None]

    def loss(self, labels, preds, weights, tag: str = "train"):
        y = labels.astype(jnp.float32)
        l = jax.vmap(self._example_loss)(preds[:, 0], y)
        return jnp.sum(weights * l) / (jnp.sum(weights) + _EPS)

    def predict_proba(self, preds):
        p1 = jax.nn.sigmoid(preds[:, 0])
        return jnp.stack([1.0 - p1, p1], axis=1)


def make_loss(name: str, task, num_classes: int):
    from ydf_tpu.config import Task

    if name in ("DEFAULT", "AUTO", None):
        if task == Task.CLASSIFICATION:
            name = (
                "BINOMIAL_LOG_LIKELIHOOD"
                if num_classes == 2
                else "MULTINOMIAL_LOG_LIKELIHOOD"
            )
        elif task in (Task.REGRESSION,):
            name = "SQUARED_ERROR"
        elif task == Task.RANKING:
            name = "LAMBDA_MART_NDCG"
        elif task == Task.SURVIVAL_ANALYSIS:
            name = "COX_PROPORTIONAL_HAZARD"
        else:
            raise ValueError(f"No default GBT loss for task {task}")
    if name == "BINOMIAL_LOG_LIKELIHOOD":
        return BinomialLogLikelihood()
    if name == "SQUARED_ERROR":
        return MeanSquaredError()
    if name == "MULTINOMIAL_LOG_LIKELIHOOD":
        return MultinomialLogLikelihood(num_classes=num_classes)
    if name == "LAMBDA_MART_NDCG":
        from ydf_tpu.learners.ranking_loss import LambdaMartNdcg

        return LambdaMartNdcg()
    if name == "XE_NDCG_MART":
        from ydf_tpu.learners.ranking_loss import XeNdcg

        return XeNdcg()
    if name == "POISSON":
        return PoissonLoss()
    if name == "MEAN_AVERAGE_ERROR":
        return MeanAverageError()
    if name == "BINARY_FOCAL_LOSS":
        return BinaryFocalLoss()
    if name == "COX_PROPORTIONAL_HAZARD":
        from ydf_tpu.learners.survival_loss import CoxProportionalHazardLoss

        return CoxProportionalHazardLoss()
    raise ValueError(f"Unknown loss {name!r}")


@dataclasses.dataclass(frozen=True)
class CustomLoss:
    """User-supplied loss (reference: pydf custom_loss.py + the C++
    custom-loss bridges, learner/custom_loss.cc): three JAX-traceable
    callables over batched arrays.

        CustomLoss(
            initial_predictions_fn=lambda y, w: jnp.zeros((1,)),
            gradient_and_hessian_fn=lambda y, s: (g, h),  # s: [n] scores
            loss_fn=lambda y, s: scalar,       # or (y, s, w) for weighted
        )

    Hashable by field identity, so the jitted boosting loop caches per
    CustomLoss instance. Single-output only (num_dims = 1).
    """

    initial_predictions_fn: object
    gradient_and_hessian_fn: object
    loss_fn: object
    name: str = "CUSTOM"

    num_dims = 1

    def initial_predictions(self, labels, weights):
        out = jnp.asarray(self.initial_predictions_fn(labels, weights))
        return out.reshape((1,)).astype(jnp.float32)

    def grad_hess(self, labels, preds):
        g, h = self.gradient_and_hessian_fn(labels, preds[:, 0])
        return (
            jnp.asarray(g).reshape(-1, 1),
            jnp.maximum(jnp.asarray(h).reshape(-1, 1), _EPS),
        )

    def loss(self, labels, preds, weights, tag: str = "train"):
        import inspect

        params = inspect.signature(self.loss_fn).parameters
        if len(params) >= 3:
            return jnp.asarray(self.loss_fn(labels, preds[:, 0], weights))
        return jnp.asarray(self.loss_fn(labels, preds[:, 0]))

    def predict_proba(self, preds):
        return preds

    def fingerprint(self) -> bytes:
        """Stable content hash for checkpoint-resume validation: the
        compiled bytecode of each user callable (a changed lambda body
        changes the fingerprint; an identical redefinition does not)."""
        out = []
        for fn in (
            self.initial_predictions_fn,
            self.gradient_and_hessian_fn,
            self.loss_fn,
        ):
            code = getattr(fn, "__code__", None)
            out.append(code.co_code if code is not None else repr(fn).encode())
        return b"|".join(out)
