"""LambdaMART NDCG ranking loss.

Re-design of the reference's NDCG loss (`ydf/learner/gradient_boosted_trees/
loss/loss_imp_ndcg.{h,cc}`, LambdaMART per Burges et al.) in batched form.
Gains are exponential (2^rel - 1) and discounts are truncated at
`ndcg_truncation` (reference default 5).

For ordered pair (i better than j):
    rho    = sigmoid(s_j - s_i)
    |ΔZ|   = |gain_i - gain_j| · |disc_i - disc_j| / maxDCG
    dL/ds_i -= rho·|ΔZ| ;  dL/ds_j += rho·|ΔZ| ;  hess += rho(1-rho)·|ΔZ|

The reported loss is -NDCG@truncation (lower is better), matching the
reference's convention.

**The tie rule.** Documents of equal score rank in dataset order within
their query: the one that comes first in the table ranks first. Before
the first tree every score is 0, so the first tree's gradients are this
rule.

**The layout** (`build_rank_groups`, `RankGroups`). The learner orders
the rows of a split by query once on the host, so that a query's
documents are consecutive rows on the device, in dataset order. Queries
are bucketed by size, one bucket a power of two (1, 2, 4, ... up to the
longest), and a bucket's `[queries, G]` view of a per-row vector is that
many slices of G consecutive rows (`to_groups`), the slots past a
query's end masked: no query is padded beyond twice its size, none to
the longest. What comes back by the row (`from_groups`) is read from the
buckets' views put end to end, through each row's slot, gradient and
hessian in one gather of pairs. (On the chip at 13.5M rows and 113k
queries, PERF.md section 6, PR 36: the slices take 0.092 s a vector, an
index matrix 0.133 s; the gather of pairs 0.140 s, one gather a vector
0.205 s for two, a scatter-add 0.200 s for one.) The structure
is data handed to the compiled program, arrays whose shapes are all it
specialises on; the loss itself is hashable by its truncation alone, so
the second `train()` on a table builds no program.

**The pairs.** |disc_i - disc_j| is exactly 0 for two documents that
both rank at or past the truncation, so every pair with a lambda has
one of the query's top `ndcg_truncation` documents in it. A bucket
therefore computes `[queries, T, G]` pair slots (T = min(truncation,
G)), each top document against every document of its query, and never a
`[G, G]` block; the top T come from T passes of arg-max (first position
wins a tie: the tie rule), so nothing is sorted. The sums are those of
the `[G, G]` formula in another order.

**One view of the training scores a tree.** `grad_hess_loss` gives a
tree's lambdas and the loss of the scores they come from out of one
`to_groups` and one pass of top documents a bucket: the DCG is read off
the lambdas' picks. Outside DART the scores a tree's lambdas come from
are the forest before it, so the boosting step reports that forest's
loss, and the chunk views its last forest once more for the loss alone
(`learners/gbt.py` `run_chunk`): four trees in one chunk make five views
of the training scores where one for the lambdas and one for the loss a
tree made eight.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


class RankGroups(NamedTuple):
    """The query structure of one split's rows, as the compiled program
    takes it. Bucket b holds the queries of more than `G_b / 2` and at
    most `G_b = len(lanes[b])` documents."""

    # Per bucket, [queries of the bucket] int32: the row of each query's
    # first document, and how many documents it trains on.
    starts: Tuple[jax.Array, ...]
    sizes: Tuple[jax.Array, ...]
    # Per bucket, arange(G_b): the bucket's width, carried as a shape.
    lanes: Tuple[jax.Array, ...]
    # [rows] int32: each row's slot in the buckets' [queries, G_b] views
    # put end to end; one past the last slot for a row no query holds
    # (mesh padding, documents past `ranking_max_group_size`).
    slot: jax.Array


def _bucket_width(size: np.ndarray) -> np.ndarray:
    """The least power of two that holds `size` (>= 1) documents."""
    width = np.ones_like(size)
    while np.any(width < size):
        width = np.where(width < size, width * 2, width)
    return width


def build_rank_groups(
    codes: np.ndarray, num_rows: Optional[int] = None,
    max_group_size: Optional[int] = None, truncation: int = 5,
):
    """(RankGroups as numpy arrays, facts) for rows ordered by query:
    `codes` [n] is non-decreasing, one value a query. `num_rows` >= n is
    the length of the per-row vectors (mesh padding rows belong to no
    query). A query longer than `max_group_size` trains on its first
    `max_group_size` documents only, with a warning; None: no cap.
    `facts`: what the program computes a tree, for the counters
    (`rank_pair_slots`, `rank_pairs`, `rank_pairs_all`, `rank_buckets`).
    """
    codes = np.asarray(codes)
    n = len(codes)
    num_rows = n if num_rows is None else num_rows
    if n and np.any(codes[1:] < codes[:-1]):
        raise ValueError("build_rank_groups: rows are not ordered by query")
    first = np.flatnonzero(np.r_[True, codes[1:] != codes[:-1]]) if n else (
        np.zeros((0,), np.int64))
    full = np.diff(np.r_[first, n])
    sizes = full
    if max_group_size is not None and n and full.max() > max_group_size:
        warnings.warn(
            f"{int(np.sum(full > max_group_size))} query group(s) exceed "
            f"max_group_size={max_group_size} (largest: {int(full.max())}); "
            "excess documents are dropped from training and NDCG. Raise "
            "ranking_max_group_size (None: no cap) to keep them.",
            stacklevel=3,
        )
        sizes = np.minimum(full, max_group_size)
    widths = _bucket_width(sizes)
    starts_b, sizes_b, lanes_b = [], [], []
    slot = np.full((num_rows,), -1, np.int64)
    offset = 0
    for G in np.unique(widths):
        q = np.flatnonzero(widths == G)
        starts_b.append(first[q].astype(np.int32))
        sizes_b.append(sizes[q].astype(np.int32))
        lanes_b.append(np.arange(G, dtype=np.int32))
        # Row first[q] + j sits at slot offset + (q's place) * G + j.
        place = np.repeat(np.arange(len(q)), sizes[q])
        lane = np.arange(sizes[q].sum()) - np.repeat(
            np.cumsum(sizes[q]) - sizes[q], sizes[q])
        slot[np.repeat(first[q], sizes[q]) + lane] = offset + place * G + lane
        offset += len(q) * int(G)
    if offset >= 2 ** 31:
        raise ValueError(f"{offset} slots do not fit an int32 index")
    slot[slot < 0] = offset
    groups = RankGroups(tuple(starts_b), tuple(sizes_b), tuple(lanes_b),
                        slot.astype(np.int32))
    top = np.minimum(truncation, sizes)
    facts = {
        "rank_pair_slots": float(np.sum(
            np.minimum(truncation, widths) * widths)),
        "rank_pairs": float(np.sum(top * sizes)),
        "rank_pairs_all": float(np.sum(sizes.astype(np.float64) ** 2)),
        "rank_buckets": float(len(starts_b)),
    }
    return groups, facts


def to_groups(groups: RankGroups, v: jax.Array, fill=0.0):
    """Per bucket, the `[queries, G_b]` view of the per-row vector `v`:
    each query's documents in dataset order, `fill` past its end."""
    widest = max((len(lane) for lane in groups.lanes), default=0)
    # Room past the end: a slice that would run over is moved back.
    v_pad = jnp.concatenate([v, jnp.full((widest,), fill, v.dtype)])
    out = []
    for starts, sizes, lanes in zip(*groups[:3]):
        G = lanes.shape[0]
        rows = jax.vmap(
            lambda st: jax.lax.dynamic_slice_in_dim(v_pad, st, G)
        )(starts)
        out.append(jnp.where(_mask(sizes, lanes), rows, fill))
    return out


def from_groups(groups: RankGroups, *per_bucket):
    """The per-row vectors of the buckets' `[queries, G_b]` arrays, one
    vector for each list `per_bucket` gives (a tuple of them): 0 for a
    row no query holds. What a view holds past a query's end is never
    read. Several vectors come through ONE gather, of a row of them a
    slot."""
    flat = jnp.concatenate(
        [
            jnp.stack([a.reshape(-1) for a in views], axis=1)
            for views in zip(*per_bucket)
        ]
        + [jnp.zeros((1, len(per_bucket)), per_bucket[0][0].dtype)]
    )
    rows = flat[groups.slot]
    return tuple(rows[:, k] for k in range(len(per_bucket)))


def _mask(sizes, lanes):
    return lanes[None, :] < sizes[:, None]


def _top_documents(score, valid, T):
    """The `T` first-ranked documents of every query of a bucket, by
    decreasing `score` [Q, G] among `valid`, the earlier position first
    among equals. Returns T one-hot masks [Q, G], the first-ranked
    document's first; a query of fewer than T documents picks nothing
    more."""
    neg = jnp.float32(-jnp.inf)
    lanes = jnp.arange(score.shape[1], dtype=jnp.int32)[None, :]
    free = valid
    out = []
    for _ in range(T):
        at = jnp.argmax(jnp.where(free, score, neg), axis=1).astype(jnp.int32)
        picked = (lanes == at[:, None]) & free
        out.append(picked)
        free = free & ~picked
    return out


def _position_discounts(T):
    return [float(1.0 / np.log2(t + 2.0)) for t in range(T)]


def _take(a, picked):
    """a[q, position picked in q] as [Q] (0 where nothing is picked)."""
    return jnp.sum(jnp.where(picked, a, 0.0), axis=1)


@dataclasses.dataclass(frozen=True)
class LambdaMartNdcg:
    """Group-structured loss: the learner hands `grad_hess` and `loss`
    the `group_context` of the split's `RankGroups`."""

    ndcg_truncation: int = 5

    name = "LAMBDA_MART_NDCG"
    num_dims = 1

    def initial_predictions(self, labels, weights):
        return jnp.zeros((1,), jnp.float32)

    def group_context(self, labels, groups: RankGroups):
        """What a split's trees share, made once outside the boosting
        scan: per bucket the relevances, validity, gains and 1 / maxDCG
        in the `[queries, G]` layout."""
        ctx = []
        with jax.named_scope("ydf.rank"):
            y_b = to_groups(groups, labels.astype(jnp.float32), -1.0)
            for y, sizes, lanes in zip(y_b, groups.sizes, groups.lanes):
                m = _mask(sizes, lanes)
                gains = jnp.where(m, jnp.exp2(y) - 1.0, 0.0)
                maxdcg = self._dcg(gains, m, gains)
                inv = jnp.where(maxdcg > 0, 1.0 / (maxdcg + _EPS), 0.0)
                ctx.append((y, m, gains, inv))
        return groups, tuple(ctx)

    def _dcg(self, score, valid, gains):
        """[Q]: the gains of each query's top documents by `score`, each
        times its position's discount."""
        T = min(self.ndcg_truncation, score.shape[1])
        return sum(
            _take(gains, picked) * d
            for picked, d in zip(
                _top_documents(score, valid, T), _position_discounts(T))
        )

    def _bucket_lambdas(self, s, y, m, gains, inv_maxdcg):
        """s, y, m, gains: [Q, G]; inv_maxdcg: [Q]. Returns (g, h) [Q, G]
        and the DCG [Q] of the same top documents."""
        T = min(self.ndcg_truncation, s.shape[1])
        top = _top_documents(s, m, T)
        discs = _position_discounts(T)
        # Every document's discount: its position's if it is in the top T.
        disc = sum(jnp.where(picked, d, 0.0) for picked, d in zip(top, discs))
        g = jnp.zeros_like(s)
        h = jnp.zeros_like(s)
        later = m
        for picked, d in zip(top, discs):
            # The pairs of this top document `a` with every document that
            # ranks after it (those before it had their turn).
            later = later & ~picked
            has = jnp.any(picked, axis=1, keepdims=True)
            s_a = _take(s, picked)[:, None]
            y_a = _take(y, picked)[:, None]
            gain_a = _take(gains, picked)[:, None]
            a_better = y_a > y
            pair = later & has & (y_a != y)
            delta = (jnp.abs(gain_a - gains) * jnp.abs(d - disc)
                     * inv_maxdcg[:, None])
            rho = jax.nn.sigmoid(jnp.where(a_better, s - s_a, s_a - s))
            lam = jnp.where(pair, rho * delta, 0.0)
            lam = jnp.where(a_better, lam, -lam)  # toward the worse one
            hl = jnp.where(pair, rho * (1.0 - rho) * delta, 0.0)
            g = g + lam + jnp.where(
                picked, -jnp.sum(lam, axis=1, keepdims=True), 0.0)
            h = h + hl + jnp.where(
                picked, jnp.sum(hl, axis=1, keepdims=True), 0.0)
        dcg = sum(_take(gains, picked) * d for picked, d in zip(top, discs))
        return g, h, dcg

    def grad_hess_loss(self, labels, preds, groups):
        """(g, h, loss) of `preds` from one view of them: the loss is
        -NDCG@truncation averaged over the queries that have a relevant
        document, the DCG taken from the lambdas' top documents."""
        groups, ctx = groups
        total = jnp.float32(0.0)
        count = jnp.float32(0.0)
        g_b, h_b = [], []
        with jax.named_scope("ydf.rank"):
            for s, c in zip(to_groups(groups, preds[:, 0]), ctx):
                g, h, dcg = self._bucket_lambdas(s, *c)
                g_b.append(g)
                h_b.append(h)
                total += jnp.sum(dcg * c[3])
                count += jnp.sum(c[3] > 0)
            g, h = from_groups(groups, g_b, h_b)
        return g[:, None], h[:, None], -total / (count + _EPS)

    def grad_hess(self, labels, preds, groups):
        return self.grad_hess_loss(labels, preds, groups)[:2]

    def loss(self, labels, preds, weights, tag: str = "train", groups=None):
        """`grad_hess_loss`'s loss: a compiled program drops the lambdas
        that nothing reads."""
        return self.grad_hess_loss(labels, preds, groups)[2]

    def predict_proba(self, preds):
        return preds


@dataclasses.dataclass(frozen=True)
class XeNdcg(LambdaMartNdcg):
    """Cross-entropy NDCG surrogate (Bruch et al. 2020; reference
    loss_imp_cross_entropy_ndcg.cc, Loss enum XE_NDCG_MART): per query
    group, the model's softmax over document scores is pulled toward the
    normalized relevance-gain distribution. Gradients are the listwise
    softmax residual, no pairs.

    Shares LambdaMartNdcg's group layout; only `grad_hess_loss`
    differs.
    """

    name = "XE_NDCG_MART"

    @staticmethod
    def _softmax_terms(s, m, gains):
        """s, m, gains: [Q, G]. Returns (p, t, valid): softmax scores and
        gain targets over the valid documents (zeros past a query's
        end), and which queries have a relevant document."""
        p = jnp.where(m, jax.nn.softmax(jnp.where(m, s, -jnp.inf), axis=1), 0.0)
        denom = jnp.sum(gains, axis=1, keepdims=True)
        # All-zero-relevance groups contribute nothing (uniform target
        # would only add noise; the reference samples relevances instead).
        t = jnp.where(denom > 0, gains / (denom + _EPS), 0.0)
        return p, t, denom > 0

    def grad_hess_loss(self, labels, preds, groups):
        """(g, h, loss) of `preds` from one view of them, the softmax
        terms shared by the gradient and the loss."""
        groups, ctx = groups
        g_b, h_b = [], []
        total = jnp.float32(0.0)
        count = jnp.float32(0.0)
        with jax.named_scope("ydf.rank"):
            for s, (_, m, gains, _inv) in zip(
                to_groups(groups, preds[:, 0]), ctx
            ):
                p, t, valid = self._softmax_terms(s, m, gains)
                g_b.append(jnp.where(valid, p - t, 0.0))
                h_b.append(jnp.where(valid, p * (1.0 - p), 0.0))
                ce = -jnp.sum(t * jnp.log(p + _EPS), axis=1, keepdims=True)
                total += jnp.sum(jnp.where(valid, ce, 0.0))
                count += jnp.sum(valid)
            g, h = from_groups(groups, g_b, h_b)
        return g[:, None], jnp.maximum(h[:, None], 1e-6), total / (
            count + _EPS)
