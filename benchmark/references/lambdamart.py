"""Plain reference for LambdaMART (NDCG) gradient-boosted trees on a
binned table of judged documents.

It imports nothing of ydf_tpu and takes nothing the program has made; of
the benchmark it takes `harness/reference.py`'s binning, routing and
block helpers, which are loss-free. From the raw table (its row
`hp["group_row"]` the query id of each document), the grades and the
configuration's hyperparameters it works out, by the published recipe
(Burges 2010, "From RankNet to LambdaRank to LambdaMART", as YDF's
`LAMBDA_MART_NDCG` states it), what a trained model has to satisfy:

  bins       as `harness/reference.py`: 256-quantile edges of a fixed
             sample of each feature column, missing values imputed with
             the column's mean
  split      by whole queries: the queries numbered by their sorted
             distinct ids, the first max(int(queries * ratio), 1) of
             `RandomState(random_seed).permutation(queries)` validate
  per tree   (the first `follow_trees`, the reference keeping its own
             scores and moving them by its OWN leaf values)
    lambdas  every document's gradient and hessian, query by query, by
             the plain [G, G] formula: gains 2^grade - 1, positions by
             decreasing score with TIES IN DATASET ORDER (a stable
             sort), discounts 1 / log2(position + 2) for the first
             `ndcg_truncation` positions and 0 after; for each pair (i
             of the higher grade, j) rho = sigmoid(s_j - s_i), |dZ| =
             |gain_i - gain_j| |disc_i - disc_j| / maxDCG, g_i -= rho
             |dZ|, g_j += rho |dZ|, h_i and h_j += rho (1 - rho) |dZ|
    leaves   every leaf's value, -shrinkage * sum(g) / sum(h), and count
             over the training rows the model's own splits send there
    loss     -NDCG@truncation averaged over the queries that have a
             relevant document, on the training and on the validation
             queries, after the tree
  tree 1     every node's split against the best split the reference's
             own histograms of those lambdas offer that node

Departures from the published description, each YDF's own and the
program's too: the hessian is not floored (a leaf's sum is what it is);
a query without a relevant document has no lambdas and is left out of
the NDCG's mean; maxDCG gets 1e-12 added before it divides; the Newton
step's denominator is sum(h) + l2 + 1e-12.

Queries are padded to the next power of two from 8 up and read in blocks
of at most 2^24 pair slots, a size class a program. The lambdas are
computed on the first device of those given (from the scores of all
parts, fetched to the host: 4 bytes a document a tree); the sums over
rows run as `harness/reference.py` runs them, in row blocks divided over
the devices, float32 at `highest`, added in float64 on the host.
"""

from __future__ import annotations

import functools
import json
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare
from harness.reference import (EPS, HIGHEST, LEAF_PAD, SLOTS, SUB, _Part,
                               _add_leaf_values, _bin_block, _in_block_order,
                               _lookup, _route_step, bin_edges, column_means,
                               node_depths)

PAIR_SLOTS = 1 << 24  # a block of queries holds at most so many pairs
LEAST_WIDTH = 8

forest_arrays = compare.forest_arrays
FOREST_KEYS = compare.FOREST_KEYS


# ----------------------------------------------------------------- host


def queries_of(ids: np.ndarray):
    """(codes [n], number of queries): each document's query, numbered by
    the sorted distinct ids."""
    uniq, codes = np.unique(ids, return_inverse=True)
    return codes.reshape(-1), len(uniq)


def validation_queries(num_queries: int, ratio: float, seed: int):
    """True for the queries of the validation split."""
    valid = np.zeros(num_queries, bool)
    if ratio > 0 and num_queries > 1:
        nv = min(max(int(num_queries * ratio), 1), num_queries - 1)
        valid[np.random.RandomState(seed).permutation(num_queries)[:nv]] = True
    return valid


class _SizeClass(NamedTuple):
    """The queries padded to one width: `rows` [Q, W] lists each query's
    documents in dataset order (the pad is `n`, a document of grade -1
    and score 0 that no query holds), `query` [Q] their numbers."""

    rows: np.ndarray
    query: np.ndarray


def size_classes(codes: np.ndarray, num_queries: int):
    n = len(codes)
    order = np.argsort(codes, kind="stable")  # by query, dataset order kept
    sizes = np.bincount(codes, minlength=num_queries)
    first = np.cumsum(sizes) - sizes
    width = np.full(num_queries, LEAST_WIDTH, np.int64)
    while np.any(width < sizes):
        width = np.where(width < sizes, width * 2, width)
    out = []
    for W in np.unique(width):
        q = np.flatnonzero(width == W)
        lane = np.arange(W)[None, :]
        at = first[q][:, None] + lane
        rows = np.where(lane < sizes[q][:, None],
                        order[np.minimum(at, n - 1)], n)
        out.append(_SizeClass(rows.astype(np.int32), q))
    return out


# --------------------------------------------------------------- device


def position_discounts(width: int, truncation: int) -> np.ndarray:
    """[width] float32: 1 / log2(position + 2) for the first `truncation`
    positions, 0 after. Worked out on the host in float64: the chip's own
    float32 logarithm is off by a few units in the last place, which
    moved every NDCG by 2e-6 of itself (PERF.md section 6, PR 36)."""
    at = np.arange(width, dtype=np.float64)
    return np.where(at < truncation, 1.0 / np.log2(at + 2.0), 0.0).astype(
        np.float32)


def _one_query(s, y, m, pos_disc):
    """The plain formula for one query: s, y, m, pos_disc [W]. Returns
    (g, h [W], NDCG, whether the query has a relevant document)."""
    gains = jnp.where(m, jnp.exp2(y) - 1.0, 0.0)
    order = jnp.argsort(-jnp.where(m, s, -jnp.inf))  # stable: ties in order
    position = jnp.argsort(order)
    disc = pos_disc[position]
    maxdcg = jnp.sum(jnp.sort(gains)[::-1] * pos_disc)
    has = maxdcg > 0
    inv = jnp.where(has, 1.0 / (maxdcg + EPS), 0.0)
    better = (y[:, None] > y[None, :]) & m[:, None] & m[None, :]
    rho = jax.nn.sigmoid(s[None, :] - s[:, None])  # rho[i, j]: s_j - s_i
    delta = (jnp.abs(gains[:, None] - gains[None, :])
             * jnp.abs(disc[:, None] - disc[None, :]) * inv)
    lam = jnp.where(better, rho * delta, 0.0)
    hl = jnp.where(better, rho * (1.0 - rho) * delta, 0.0)
    g = -jnp.sum(lam, axis=1) + jnp.sum(lam, axis=0)
    h = jnp.sum(hl, axis=1) + jnp.sum(hl, axis=0)
    ndcg = jnp.sum(gains[order] * pos_disc) * inv
    return g, h, ndcg, has


@jax.jit
def _query_blocks(rows, s_pad, y_pad, pos_disc):
    """rows [blocks, Q, W] into the padded score and grade vectors."""

    def block(r):
        m = r < s_pad.shape[0] - 1
        return jax.vmap(lambda s, y, mm: _one_query(s, y, mm, pos_disc))(
            s_pad[r], y_pad[r], m)

    return jax.lax.map(block, rows)


@functools.partial(jax.jit, static_argnames=("num_bins",))
def _level_hist(bins, slot, stats, num_bins):
    """`harness/reference.py` `_level_hist` over given per-row stats
    [blocks, block, 3]: per block of rows, their sums in each (slot,
    feature, bin), [blocks, F, num_bins, SLOTS * 3] float32."""
    bvals = jnp.arange(num_bins, dtype=jnp.int32)

    def block(args):
        b_blk, s_blk, st_blk = args
        a = (s_blk[:, None] == jnp.arange(SLOTS)[None, :]).astype(jnp.float32)
        a = (a[:, :, None] * st_blk[:, None, :]).reshape(s_blk.shape[0], -1)
        # float32 as three bfloat16 pieces: the one-hot operand is exact
        # in bfloat16, so one bfloat16 pass over the pieces side by side
        # is what `highest` computes in six.
        hi = a.astype(jnp.bfloat16)
        mid = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        lo = (a - hi.astype(jnp.float32) - mid.astype(jnp.float32)).astype(
            jnp.bfloat16)
        pieces = jnp.concatenate([hi, mid, lo], axis=1)

        def feat(col):
            oh = (col.astype(jnp.int32)[:, None] == bvals[None, :])
            out = jax.lax.dot_general(
                oh.astype(jnp.bfloat16), pieces, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return out.reshape(num_bins, 3, -1).sum(axis=1)

        return jax.lax.map(feat, b_blk)

    return jax.lax.map(block, (jnp.swapaxes(bins, 0, 1), slot, stats))


@jax.jit
def _leaf_sums(leaf_slot, stats):
    """[blocks, block/SUB, LEAF_PAD, 3] float32 partial sums of the
    per-row stats over the rows of each leaf."""

    def block(args):
        l_blk, st_blk = args
        oh = (l_blk[:, None] == jnp.arange(LEAF_PAD)[None, :])
        oh = oh.astype(jnp.float32).reshape(-1, SUB, LEAF_PAD)
        return jnp.einsum("ksl,ksc->klc", oh, st_blk.reshape(-1, SUB, 3),
                          precision=HIGHEST)

    return jax.lax.map(block, (leaf_slot, stats))


# ------------------------------------------------------------ reference


class RankReference:
    """Holds the reference's own bins, scores and lambdas for one table."""

    def __init__(self, x, y, hp, block_rows=1 << 19, devices=None):
        """x: float32 [F + 1, n] raw table, row hp["group_row"] the query
        ids; y: [n] grades; hp: the configuration's `reference` block
        (group_row, ndcg_truncation, num_bins, validation_ratio,
        random_seed, shrinkage, max_depth, min_examples,
        l2_regularization); devices: the chips to divide the row blocks
        over, the first device if None."""
        self.hp = hp
        group_row = hp["group_row"] % x.shape[0]
        ids = x[group_row]
        keep = np.delete(np.arange(x.shape[0]), group_row)
        # (a view where the id row is the first or the last: no copy)
        x = (x[keep[0]:keep[-1] + 1]
             if group_row in (0, x.shape[0] - 1) else x[keep])
        self.F, self.n = x.shape
        self.block = block_rows
        if block_rows % SUB:
            raise ValueError("block_rows must be a multiple of SUB")
        self.blocks = (self.n + block_rows - 1) // block_rows
        devices = list(devices) if devices else [jax.devices()[0]]
        cuts = [self.blocks * d // len(devices)
                for d in range(len(devices) + 1)]
        self.parts = [_Part(dev, lo, hi) for dev, lo, hi
                      in zip(devices, cuts, cuts[1:]) if hi > lo]
        t0 = time.perf_counter()
        self.pad = self.blocks * block_rows - self.n
        self.means = column_means(x)
        self.edges = bin_edges(x, self.means, hp["num_bins"])
        codes, self.num_queries = queries_of(ids)
        self.valid_query = validation_queries(
            self.num_queries, hp["validation_ratio"], hp["random_seed"])
        valid = self.valid_query[codes]
        self.classes = size_classes(codes, self.num_queries)
        t1 = time.perf_counter()

        def binned(p):  # a part's bins, [F, its blocks, block] uint8
            means, edges = p.put(self.means), p.put(self.edges)
            cols = []
            for b in range(p.lo, p.hi):
                lo = b * block_rows
                xb = np.zeros((self.F, block_rows), np.float32)
                xb[:, :min(block_rows, self.n - lo)] = x[:, lo:lo + block_rows]
                cols.append(_bin_block(p.put(xb), means, edges))
            return jnp.stack(cols, axis=1)

        self.bins = [binned(p) for p in self.parts]
        self.w_tr = self._on_parts((~valid).astype(np.float32))
        self.n_tr = float(self.n - valid.sum())
        first = self.parts[0]
        self.y_pad = first.put(np.r_[y.astype(np.float32), np.float32(-1)])
        self.class_rows = []
        for c in self.classes:  # [blocks, Q, W], a block at most PAIR_SLOTS
            Q, W = c.rows.shape
            per = max(1, min(Q, PAIR_SLOTS // (W * W)))
            blocks = (Q + per - 1) // per
            rows = np.full((blocks * per, W), self.n, np.int32)
            rows[:Q] = c.rows
            self.class_rows.append((
                first.put(rows.reshape(blocks, per, W)),
                first.put(position_discounts(W, hp["ndcg_truncation"]))))
        jax.block_until_ready((self.bins, self.w_tr, self.class_rows))
        self.seconds = {"host_bins": t1 - t0,
                        "upload": time.perf_counter() - t1, "lambdas": 0.0}
        self.initial_prediction = 0.0
        self.reset()

    def _on_parts(self, a):
        """[n, ...] per-row values as each part's [blocks, block, ...]."""
        a = np.pad(a, [(0, self.pad)] + [(0, 0)] * (a.ndim - 1))
        a = a.reshape((self.blocks, self.block) + a.shape[1:])
        return [p.put(a[p.lo:p.hi]) for p in self.parts]

    def reset(self):
        """Back to before the first tree: every score 0, so the lambdas
        are the tie rule's."""
        self.pred = [jnp.zeros((p.hi - p.lo, self.block), jnp.float32,
                               device=p.device) for p in self.parts]
        self._read_queries()

    def _read_queries(self):
        """From the current scores: every document's lambda stats (times
        the training weight, on the parts) and the NDCG of both splits."""
        t0 = time.perf_counter()
        s = np.concatenate([np.asarray(p).reshape(-1) for p in self.pred])
        s_pad = self.parts[0].put(np.r_[s[:self.n], np.float32(0)])
        g = np.zeros(self.n + 1, np.float32)
        h = np.zeros(self.n + 1, np.float32)
        ndcg = np.zeros(self.num_queries)
        has = np.zeros(self.num_queries, bool)
        results = [_query_blocks(rows, s_pad, self.y_pad, pos_disc)
                   for rows, pos_disc in self.class_rows]
        for c, (cg, ch, cn, ck) in zip(self.classes, results):
            Q, W = c.rows.shape
            g[c.rows] = np.asarray(cg).reshape(-1, W)[:Q]  # pads land on n
            h[c.rows] = np.asarray(ch).reshape(-1, W)[:Q]
            ndcg[c.query] = np.asarray(cn, np.float64).reshape(-1)[:Q]
            has[c.query] = np.asarray(ck).reshape(-1)[:Q]
        stats = np.stack([g[:self.n], h[:self.n],
                          np.ones(self.n, np.float32)], axis=-1)
        self.stats = [st * w[..., None] for st, w
                      in zip(self._on_parts(stats), self.w_tr)]

        def mean(queries):
            return -float(ndcg[queries].sum() / (queries.sum() + EPS))

        self.train_loss = mean(has & ~self.valid_query)
        self.valid_loss = (mean(has & self.valid_query)
                           if self.valid_query.any() else None)
        self.seconds["lambdas"] += time.perf_counter() - t0

    # -- one tree of the model ------------------------------------------

    def _grid_bins(self, tree):
        """The bin index of every split's threshold on the reference's
        own edges, and how many thresholds are on no edge."""
        thr_bin = np.zeros(len(tree["feature"]), np.int32)
        off = 0
        for i in range(int(tree["num_nodes"])):
            if tree["is_leaf"][i]:
                continue
            hits = np.flatnonzero(
                self.edges[tree["feature"][i]] == tree["threshold"][i])
            if len(hits) == 1:
                thr_bin[i] = hits[0]
            else:
                off += 1
                thr_bin[i] = np.searchsorted(
                    self.edges[tree["feature"][i]], tree["threshold"][i])
        return thr_bin, off

    def follow_tree(self, tree, with_regret: bool):
        """`tree`: the model's arrays for one tree. Moves the reference's
        scores by its own leaf values and reads the queries again.
        Returns the readings for this tree."""
        hp = self.hp
        N = int(tree["num_nodes"])
        is_leaf = np.asarray(tree["is_leaf"]).astype(bool).copy()
        is_leaf[N:] = True
        left = np.where(is_leaf, 0, tree["left"]).astype(np.int32)
        right = np.where(is_leaf, 0, tree["right"]).astype(np.int32)
        depth = node_depths(left, right, is_leaf, N)
        thr_bin, off_grid = self._grid_bins(tree)
        tables = (np.where(is_leaf, 0, tree["feature"]).astype(np.int32),
                  thr_bin, left, right, is_leaf)
        tables = [[p.put(a) for a in tables] for p in self.parts]
        node = [jnp.zeros((p.hi - p.lo, self.block), jnp.int32,
                          device=p.device) for p in self.parts]
        at_depth = []
        for _ in range(int(depth.max())):
            at_depth.append(node)
            node = [_route_step(nd, bins, *tb)
                    for nd, bins, tb in zip(node, self.bins, tables)]
        out = {"thresholds_off_grid": off_grid}
        if with_regret:
            out["split_regret"] = self._regret(
                tree, at_depth, depth, is_leaf, thr_bin, N)
        del at_depth

        leaves = np.flatnonzero(is_leaf[:N] & (depth[:N] >= 0))
        if len(leaves) > LEAF_PAD:
            raise ValueError(f"{len(leaves)} leaves exceed {LEAF_PAD}")
        slot_of = np.full(len(is_leaf), LEAF_PAD, np.int32)
        slot_of[leaves] = np.arange(len(leaves))
        sums = _in_block_order([
            _leaf_sums(_lookup(p.put(slot_of), nd), st)
            for p, nd, st in zip(self.parts, node, self.stats)])
        sums = sums.sum(axis=(0, 1))[:len(leaves)]
        ref = -hp["shrinkage"] * sums[:, 0] / (
            sums[:, 1] + hp["l2_regularization"] + EPS)
        got = np.asarray(tree["leaf_value"], np.float64)[leaves]
        scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
        out["leaf_gaps"] = np.abs(got - ref) / scale
        out["leaf_gap"] = float(np.max(out["leaf_gaps"]))
        out["leaf_rows_gap"] = float(
            np.max(np.abs(np.asarray(tree["cover"], np.float64)[leaves]
                          - sums[:, 2])))

        values = np.zeros(len(is_leaf), np.float32)
        values[leaves] = ref
        self.pred = [_add_leaf_values(pred, nd, p.put(values))
                     for p, pred, nd in zip(self.parts, self.pred, node)]
        self._read_queries()
        out["train_loss"] = self.train_loss
        out["valid_loss"] = self.valid_loss
        return out

    # -- tree 1: every split against the best on offer --------------------

    def _regret(self, tree, at_depth, depth, is_leaf, thr_bin, N):
        hp = self.hp
        B = hp["num_bins"]
        l2 = hp["l2_regularization"] + EPS
        worst = 0.0
        for d, node in enumerate(at_depth):
            ids = np.flatnonzero(depth[:N] == d)
            best = np.zeros(len(ids))
            chosen = np.zeros(len(ids))
            for lo in range(0, len(ids), SLOTS):
                group = ids[lo:lo + SLOTS]
                slot_of = np.full(len(is_leaf), -1, np.int32)
                slot_of[group] = np.arange(len(group))
                h = _in_block_order([
                    _level_hist(bins, _lookup(p.put(slot_of), nd), st,
                                num_bins=B)
                    for p, bins, nd, st in zip(
                        self.parts, self.bins, node, self.stats)]).sum(axis=0)
                h = h.reshape(self.F, B, SLOTS, 3).transpose(2, 0, 1, 3)
                for s, i in enumerate(group):
                    lt = np.cumsum(h[s], axis=1)[:, :-1]  # bin <= t
                    tot = h[s, 0].sum(axis=0)
                    rt = tot[None, None, :] - lt
                    gain = 0.5 * (lt[..., 0] ** 2 / (lt[..., 1] + l2)
                                  + rt[..., 0] ** 2 / (rt[..., 1] + l2)
                                  - tot[0] ** 2 / (tot[1] + l2))
                    ok = ((lt[..., 2] >= hp["min_examples"])
                          & (rt[..., 2] >= hp["min_examples"])
                          & np.isfinite(self.edges))
                    gain = np.where(ok, gain, -np.inf)
                    best[lo + s] = max(float(gain.max()), 0.0)
                    if not is_leaf[i]:
                        chosen[lo + s] = gain[tree["feature"][i], thr_bin[i]]
            split = ~is_leaf[ids]
            for k in range(len(ids)):
                if best[k] <= 0:
                    continue
                r = (best[k] - chosen[k]) / best[k] if split[k] else 1.0
                worst = max(worst, float(r))
        return worst


def reference(x, y, hp, block_rows=1 << 19, devices=None):
    """A RankReference for this table, to read several models against it
    (`readings(..., ref=...)`; tools/named_limits.py)."""
    with jax.default_matmul_precision("highest"):
        return RankReference(x, y, hp, block_rows=block_rows, devices=devices)


def readings(x, y, hp, jobs, follow_trees=3, devices=None,
             block_rows=1 << 19, ref=None):
    """{name: number} for the last job of `jobs` (forest_arrays of each
    job the window finished), by the reference run over the raw table:
    the eleven readings `harness/compare.py` `readings` gives a pointwise
    cell, under the same names. `ref`: a RankReference already built for
    this table (tools/named_limits.py)."""
    got = jobs[-1]
    with jax.default_matmul_precision("highest"):
        if ref is None:
            ref = reference(x, y, hp, block_rows, devices)
        else:
            ref.reset()
        t_trees = time.perf_counter()
        trees = min(follow_trees, len(got["train_loss"]))
        out = {
            "jobs_differ": compare.jobs_differ(jobs),
            "bin_edges_differ": int(np.sum(got["bin_edges"] != ref.edges)),
            "init_gap": abs(got["initial_prediction"]
                            - ref.initial_prediction),
            "thresholds_off_grid": 0, "leaf_rows_gap": 0.0, "leaf_gap": 0.0,
            "train_loss_gap": 0.0, "valid_loss_gap": 0.0,
        }
        leaf_gaps = []
        if trees < follow_trees:
            out["trees_missing"] = follow_trees - trees
        for t in range(trees):
            tree = {k: got[k][t] for k in FOREST_KEYS}
            r = ref.follow_tree(tree, with_regret=(t == 0))
            if t == 0:
                out["split_regret"] = r["split_regret"]
            out["thresholds_off_grid"] += r["thresholds_off_grid"]
            leaf_gaps.append(r["leaf_gaps"])
            for k in ("leaf_rows_gap", "leaf_gap"):
                out[k] = max(out[k], r[k])
            out["train_loss_gap"] = max(
                out["train_loss_gap"],
                abs(got["train_loss"][t] - r["train_loss"])
                / abs(r["train_loss"]))
            if r["valid_loss"] is not None and got["valid_loss"] is not None:
                out["valid_loss_gap"] = max(
                    out["valid_loss_gap"],
                    abs(got["valid_loss"][t] - r["valid_loss"])
                    / abs(r["valid_loss"]))
        if leaf_gaps:
            out["leaf_gap_median"] = float(
                np.median(np.concatenate(leaf_gaps)))
    ref.seconds["trees"] = time.perf_counter() - t_trees
    print("[reference.phases] " + json.dumps(dict(
        ref.seconds, chips=len(ref.parts), blocks=ref.blocks,
        queries=ref.num_queries)), file=sys.stderr, flush=True)
    return out
