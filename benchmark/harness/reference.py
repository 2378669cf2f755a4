"""Plain reference for gradient-boosted trees on a binned table (binary
log-likelihood or squared error).

It imports nothing of ydf_tpu and takes nothing the program has made.
From the raw table, the labels and the configuration's hyperparameters
it works out, by the published recipe (YDF's GBT: Newton boosting on the
loss's gradient and hessian, hessian gain, quantile bins), what a trained
model has to satisfy, and reads how far a model the timed path produced
lies from that:

  bins       256-quantile edges of a fixed 200,000-row sample of each
             column (missing values imputed with the column's mean), the
             deterministic 10 % validation split
  per tree   (the first `follow_trees`, the reference keeping its own
             predictions and moving them by its OWN leaf values)
    leaves   every leaf's value, -shrinkage * sum(g) / sum(h), over the
             rows the model's own splits send there, sums exact to ~1e-8
             (4,096-row partial sums in float32, added in float64)
    loss     training and validation loss after the tree, as YDF reports
             it (binomial deviance, or the root of the mean squared error)
  tree 1     every node's split against the best split the reference's
             own histograms offer that node (gain regret), and, where
             the frontier cap chose which nodes to split, against the
             nodes it left unsplit

The rows are routed by the model's own conditions, as a served model's
reference is run over the tokens that were served: a comparison tree by
tree would fail sound runs on every near-tie between two cuts.

All heavy sums run in row blocks, float32 at `highest` with exact
one-hot operands; what is added across blocks is added in float64 on the
host. The blocks are divided over the devices the reference is given
(the cell's chips), consecutive blocks to a device, so that a table only
four chips hold can be read: each device runs the same one-device
programs over its own blocks, all devices at once, and their per-block
partial sums are put end to end in block order before the host adds
them. No mesh, no collective; with one device it is one part.
"""

from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

BIN_SAMPLE_ROWS = 200_000
BIN_SAMPLE_SEED = 0xB1A5
EPS = 1e-12
SUB = 1 << 12  # rows per exact partial sum
SLOTS = 32  # nodes per histogram pass
LEAF_PAD = 128
HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------- host


def validation_mask(n: int, ratio: float, seed: int) -> np.ndarray:
    """True for the rows of the validation split: the first
    int(n * ratio) of a seeded permutation (YDF's deterministic split)."""
    valid = np.zeros(n, bool)
    if ratio > 0:
        nv = min(max(int(n * ratio), 1), n - 1)
        valid[np.random.RandomState(seed).permutation(n)[:nv]] = True
    return valid


def _per_column(fn, columns):
    """[fn(i, column) ...]: numpy's sorts and sums release the
    interpreter lock, so a thread a core goes through wide tables faster
    (four threads took 16 s over 224M x 28, half the host's part)."""
    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as pool:
        return list(pool.map(lambda ic: fn(*ic), enumerate(columns)))


def column_means(x: np.ndarray) -> np.ndarray:
    """Mean of each column's present values, in float64, as float32."""
    def mean(_i, col):
        ok = col[~np.isnan(col)]
        return np.float32(ok.mean(dtype=np.float64)) if ok.size else 0.0

    return np.asarray(_per_column(mean, x), np.float32)


def bin_edges(x: np.ndarray, means: np.ndarray, num_bins: int) -> np.ndarray:
    """[F, num_bins - 1] float32 edges, +inf past a column's last one:
    the distinct (num_bins - 1) inner quantiles of the column's sample."""
    n = x.shape[1]
    idx = None
    if n > BIN_SAMPLE_ROWS:
        idx = np.random.default_rng(BIN_SAMPLE_SEED).choice(
            n, BIN_SAMPLE_ROWS, replace=False)
    qs = np.linspace(0, 1, num_bins + 1)[1:-1]

    def column_edges(i, col):
        sample = col if idx is None else col[idx]
        sample = np.where(np.isnan(sample), means[i], sample)
        distinct = np.unique(sample)
        if len(distinct) <= num_bins - 1:
            return ((distinct[:-1] + distinct[1:]) / 2).astype(np.float32)
        return np.unique(np.quantile(sample.astype(np.float64), qs,
                                     method="linear")).astype(np.float32)

    edges = np.full((x.shape[0], num_bins - 1), np.inf, np.float32)
    for i, e in enumerate(_per_column(column_edges, x)):
        edges[i, :len(e)] = e
    return edges


# --------------------------------------------------------------- device


@jax.jit
def _bin_block(xb, means, edges):
    v = jnp.where(jnp.isnan(xb), means[:, None], xb)

    def one(args):
        col, e = args
        return jnp.sum(col[:, None] >= e[None, :], axis=1).astype(jnp.uint8)

    return jax.lax.map(one, (v, edges))


def _lookup(table, node):
    return jnp.take(table, node, axis=0)


@jax.jit
def _route_step(node, bins, feature, thr_bin, left, right, is_leaf):
    """One level down the model's tree for every row."""
    f = _lookup(feature, node)

    def pick(k, acc):
        return jnp.where(f == k, bins[k].astype(jnp.int32), acc)

    b = jax.lax.fori_loop(0, bins.shape[0], pick, jnp.zeros_like(node))
    nxt = jnp.where(b <= _lookup(thr_bin, node), _lookup(left, node),
                    _lookup(right, node))
    return jnp.where(_lookup(is_leaf, node), node, nxt)


def _stats(pred, y, w, loss):
    """[..., 3]: gradient, hessian and count of each row, times `w`."""
    if loss == "squared_error":
        return jnp.stack([(pred - y) * w, w, w], axis=-1)
    p = jax.nn.sigmoid(pred)
    return jnp.stack([(p - y) * w, p * (1.0 - p) * w, w], axis=-1)


@functools.partial(jax.jit, static_argnames=("num_bins", "loss"))
def _level_hist(bins, slot, pred, y, w, num_bins, loss):
    """[blocks, F, num_bins, SLOTS * 3] float32: per block of rows, the
    sums of (g, h, 1) over the training rows in each (slot, feature,
    bin)."""
    stats = _stats(pred, y, w, loss)
    bvals = jnp.arange(num_bins, dtype=jnp.int32)

    def block(args):
        b_blk, s_blk, st_blk = args
        a = (s_blk[:, None] == jnp.arange(SLOTS)[None, :]).astype(jnp.float32)
        a = (a[:, :, None] * st_blk[:, None, :]).reshape(s_blk.shape[0], -1)
        # float32 as three bfloat16 pieces (8 + 8 + 8 mantissa bits): the
        # one-hot operand is exact in bfloat16, so one bfloat16 pass over
        # the pieces side by side is what `highest` computes in six.
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


@functools.partial(jax.jit, static_argnames=("loss",))
def _leaf_sums(leaf_slot, pred, y, w, loss):
    """[blocks, block/SUB, LEAF_PAD, 3] float32 partial sums of (g, h, 1)
    over the training rows of each leaf."""
    stats = _stats(pred, y, w, loss)

    def block(args):
        l_blk, st_blk = args
        oh = (l_blk[:, None] == jnp.arange(LEAF_PAD)[None, :])
        oh = oh.astype(jnp.float32).reshape(-1, SUB, LEAF_PAD)
        return jnp.einsum("ksl,ksc->klc", oh, st_blk.reshape(-1, SUB, 3),
                          precision=HIGHEST)

    return jax.lax.map(block, (leaf_slot, stats))


@functools.partial(jax.jit, static_argnames=("loss",))
def _loss_sums(pred, y, w_tr, w_va, loss):
    if loss == "squared_error":
        ll = jnp.square(pred - y)
    else:
        ll = jax.nn.softplus(pred) - y * pred
    shape = (pred.shape[0], -1, SUB // 4)
    return jnp.stack([jnp.sum((ll * w_tr).reshape(shape), axis=-1),
                      jnp.sum((ll * w_va).reshape(shape), axis=-1)], axis=-1)


@jax.jit
def _add_leaf_values(pred, node, values):
    return pred + _lookup(values, node)


# ------------------------------------------------------------ reference


def node_depths(left, right, is_leaf, num_nodes):
    depth = np.full(len(left), -1, np.int64)
    depth[0] = 0
    for i in range(num_nodes):  # children are allocated after parents
        if depth[i] >= 0 and not is_leaf[i]:
            depth[left[i]] = depth[right[i]] = depth[i] + 1
    return depth


class _Part:
    """One device's run of consecutive row blocks, [lo, hi)."""

    def __init__(self, device, lo, hi):
        self.device, self.lo, self.hi = device, lo, hi

    def put(self, a):
        return jax.device_put(a, self.device)


def _in_block_order(results):
    """The parts' per-block partial sums as one float64 array, blocks in
    table order. Called after every part's program is enqueued, so the
    devices work side by side while the host waits for the first."""
    if len(results) == 1:
        return np.asarray(results[0], np.float64)
    out = np.empty((sum(r.shape[0] for r in results),) + results[0].shape[1:],
                   np.float64)
    lo = 0
    for r in results:  # widened as it is copied in: no second copy
        out[lo:lo + r.shape[0]] = np.asarray(r)
        lo += r.shape[0]
    return out


class GbtReference:
    """Holds the reference's own bins and predictions for one table."""

    def __init__(self, x, y, hp, block_rows=1 << 19, devices=None):
        """x: float32 [F, n] raw table; y: [n] targets, or labels in
        {0, 1} (class 1 of the model is the rarer label, by YDF's
        dictionary order); hp: the configuration's hyperparameters (loss,
        num_bins, validation_ratio, random_seed, shrinkage, max_depth,
        max_frontier, min_examples, l2_regularization); devices: the
        chips to divide the row blocks over, the first device if None."""
        self.loss = hp["loss"]
        if self.loss not in ("binomial", "squared_error"):
            raise ValueError(f"no reference for loss {self.loss!r}")
        self.hp = hp
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
        pad = self.blocks * block_rows - self.n
        if self.loss == "binomial":
            counts = np.bincount(y, minlength=2)
            y = (y != int(np.argmax(counts))).astype(np.float32)
        shape = (self.blocks, block_rows)

        def on_devices(a, dtype):
            a = np.pad(a.astype(dtype), (0, pad)).reshape(shape)
            return [p.put(a[p.lo:p.hi]) for p in self.parts]

        def binned(p):  # a part's bins, [F, its blocks, block] uint8
            means, edges = p.put(self.means), p.put(self.edges)
            cols = []
            for b in range(p.lo, p.hi):
                lo = b * block_rows
                xb = np.zeros((self.F, block_rows), np.float32)
                xb[:, :min(block_rows, self.n - lo)] = x[:, lo:lo + block_rows]
                cols.append(_bin_block(p.put(xb), means, edges))
            return jnp.stack(cols, axis=1)

        # The split's permutation of all rows (17 s at 224M) runs beside
        # the bins; a thread a part sends them: one thread's cutting and
        # sending of 58 MB blocks feeds one chip at 0.6 GB/s.
        with ThreadPoolExecutor(len(self.parts) + 1) as pool:
            split = pool.submit(validation_mask, self.n,
                                hp["validation_ratio"], hp["random_seed"])
            self.means = column_means(x)
            self.edges = bin_edges(x, self.means, hp["num_bins"])
            t1 = time.perf_counter()
            self.bins = list(pool.map(binned, self.parts))
            valid = split.result()
        self.y = on_devices(y, np.float32)
        self.w_tr = on_devices(~valid, np.float32)
        self.w_va = on_devices(valid, np.float32)
        self.n_tr = float(self.n - valid.sum())
        self.n_va = float(valid.sum())
        jax.block_until_ready((self.bins, self.y, self.w_tr, self.w_va))
        self.seconds = {"host_bins": t1 - t0,
                        "upload": time.perf_counter() - t1}
        mean = float(y[~valid].mean(dtype=np.float64))
        if self.loss == "binomial":
            p = min(max(mean, EPS), 1 - EPS)
            mean = float(np.log(p / (1 - p)))
        self.initial_prediction = mean
        self.reset()

    def reset(self):
        """Back to before the first tree."""
        self.pred = [
            jnp.full((p.hi - p.lo, self.block), self.initial_prediction,
                     jnp.float32, device=p.device) for p in self.parts]

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
        """`tree`: the model's arrays for one tree (feature, threshold,
        left, right, is_leaf, leaf_value [N], num_nodes). Moves the
        reference's predictions by its own leaf values. Returns the
        readings for this tree."""
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
            _leaf_sums(_lookup(p.put(slot_of), nd), pred, y, w, loss=self.loss)
            for p, nd, pred, y, w
            in zip(self.parts, node, self.pred, self.y, self.w_tr)])
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
        sums = _in_block_order([
            _loss_sums(pred, y, w_tr, w_va, loss=self.loss)
            for pred, y, w_tr, w_va
            in zip(self.pred, self.y, self.w_tr, self.w_va)]).sum(axis=(0, 1))

        def reported(total, n):
            mean = total / (n + EPS)
            return np.sqrt(mean) if self.loss == "squared_error" else 2 * mean

        out["train_loss"] = reported(sums[0], self.n_tr)
        out["valid_loss"] = reported(sums[1], self.n_va) if self.n_va else None
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
                    _level_hist(bins, _lookup(p.put(slot_of), nd), pred, y, w,
                                num_bins=B, loss=self.loss)
                    for p, bins, nd, pred, y, w in zip(
                        self.parts, self.bins, node, self.pred, self.y,
                        self.w_tr)]).sum(axis=0)
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
            floor = best[split].min() if split.any() else 0.0
            # The frontier holds `max_frontier` nodes: where a level's
            # children would not fit, only its best half-frontier splits.
            cap = hp.get("max_frontier")
            capped = (cap is not None and d < hp["max_depth"] - 1
                      and 2 * len(ids) > cap)
            for k in range(len(ids)):
                if best[k] <= 0:
                    continue
                if split[k]:
                    r = (best[k] - chosen[k]) / best[k]
                elif capped:  # left unsplit by the cap: no better than kept
                    r = max(best[k] - floor, 0.0) / best[k]
                else:
                    r = 1.0
                worst = max(worst, float(r))
        return worst
