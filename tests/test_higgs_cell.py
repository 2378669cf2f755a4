"""The binary-classification cell `higgs_gbt.sweep` (PR 28) at a size a
CPU run can hold, and what came with it: the histogram's chunk loop,
whose compile cost on a TPU may not depend on the number of chunks; the
configuration's files; the seconds of the boosting program's build that
every job reports.
"""

import copy
import functools
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest, runner  # noqa: E402
from harness.datagen import as_columns, make_table  # noqa: E402

CELL = "higgs_gbt.sweep"
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "higgs_small_chunks_forest.npz")


# ------------------------------------------------- (a) against the reference


def small_files(rows=40_000, depth=4):
    """The cell's own files with the rows and the depth cut, as
    benchmark/tests/small.py cuts the squared-error cell."""
    m = manifest.load()
    files = list(manifest.cell_files(m, CELL))
    cfg = copy.deepcopy(files[2])
    cfg["rows"] = rows
    cfg["hyperparameters"]["max_depth"] = depth
    cfg["reference"]["max_depth"] = depth
    files[2] = cfg
    return m, tuple(files)


def run_small(seed=17):
    m, files = small_files()
    _, result = runner.run_cell(m, CELL, seed, 0.5, 0, time.time(),
                                check_kwargs={"block_rows": 1 << 14},
                                files=files)
    return result


def test_binary_cell_agrees_with_the_binomial_reference():
    result = run_small()
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_rows_trees_per_s"}


def test_int8_control_is_not_correct():
    """The program's own 8-bit histogram, switched on in a fresh process
    (the switch is read when the boosting loop is first traced)."""
    script = (
        "import json, sys\n"
        f"sys.path.insert(0, {os.path.dirname(__file__)!r})\n"
        "from test_higgs_cell import run_small\n"
        "r = run_small()\n"
        "print(json.dumps({'correct': r['correct'],"
        " 'compared': r['compared']}))\n"
    )
    env = dict(os.environ, YDF_TPU_HIST_QUANT="int8", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] is False, got["compared"]


def test_altered_split_is_not_correct(monkeypatch):
    """Tree 1's root cut moved off the bin grid."""
    real = ydf.GradientBoostedTreesLearner

    class Altered:
        def __init__(self, model):
            self._model, self.forest = model, self

        def __getattr__(self, name):
            return getattr(self._model, name)

        def to_numpy(self):
            arrays = {k: np.array(v)
                      for k, v in self._model.forest.to_numpy().items()}
            arrays["threshold"][0, 0] += 0.25
            return arrays

    class Learner(real):
        def train(self, ds, valid=None):
            return Altered(super().train(ds, valid=valid))

    monkeypatch.setattr(ydf, "GradientBoostedTreesLearner", Learner)
    result = run_small()
    assert not result["correct"], result["compared"]


# --------------------------------------------------- (b) the chunk loop's form


def _lowered_histogram(chunks, chunk=1024, tail=0):
    import jax
    import jax.numpy as jnp

    from ydf_tpu.ops.histogram import histogram

    n = chunks * chunk + tail
    fn = jax.jit(lambda b, s, st: histogram(
        b, s, st, num_slots=2, num_bins=16, impl="matmul", chunk=chunk))
    return n, fn.lower(
        jax.ShapeDtypeStruct((n, 3), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n, 3), jnp.float32)).as_text()


def test_chunk_loop_holds_the_chunk_count_in_its_bound_alone():
    """What made the TPU compile cost by the count (62 chunks x 28
    features: over 400 s, 64: 2 s) was an operand laid out anew as
    [chunks, chunk, ...]. The loop slices whole chunks out of the
    operands as they come, so the count is one scalar, the bound."""
    texts = {}
    for chunks in (5, 7):
        n, text = _lowered_histogram(chunks)
        assert not re.search(rf"tensor<{chunks}x1024[x>]", text)
        assert "stablehlo.pad" not in text  # whole chunks: nothing padded
        text = re.sub(rf"(?<!\d){n}(?!\d)", "ROWS", text)
        assert text.count(f"dense<{chunks}> : tensor<i32>") == 1
        texts[chunks] = text.replace(f"dense<{chunks}> : tensor<i32>",
                                     "dense<CHUNKS> : tensor<i32>")
    assert texts[5] == texts[7]


def test_ragged_tail_is_padded_alone():
    """The rows past the last whole chunk are one more call of the
    chunk's body on their own padded copy: no pad is as long as the
    table."""
    n, text = _lowered_histogram(5, tail=100)
    pads = re.findall(r"stablehlo\.pad.*-> tensor<(\d+)[x>]", text)
    assert pads and all(int(p) == 1024 for p in pads), pads
    assert not re.search(r"tensor<[56]x1024[x>]", text)


def _forest_with_small_chunks(monkeypatch, route_impl, chunk=512):
    """Three trees on 3,000 rows (2,700 training rows: five chunks of 512
    and a tail of 140) through the `matmul` histogram."""
    import jax

    from ydf_tpu.learners import gbt
    from ydf_tpu.ops import grower

    monkeypatch.setenv("YDF_TPU_HIST_IMPL", "matmul")
    monkeypatch.setenv("YDF_TPU_ROUTE_IMPL", route_impl)
    monkeypatch.setattr(grower, "histogram",
                        functools.partial(grower.histogram, chunk=chunk))
    jax.clear_caches()
    gbt._make_boost_fn.cache_clear()
    try:
        x, y = make_table(3000, 28, 11, "binary_logit")
        model = ydf.GradientBoostedTreesLearner(
            label="label", task=Task.CLASSIFICATION, num_trees=3,
            max_depth=4).train(as_columns(x, y))
        impl = model.training_logs["implementations"]
        assert (impl["hist_impl"], impl["route_impl"]) == ("matmul",
                                                           route_impl)
        return {k: np.asarray(v)
                for k, v in model.forest.to_numpy().items()}
    finally:
        jax.clear_caches()
        gbt._make_boost_fn.cache_clear()


def _assert_same_arrays(got, want):
    assert set(got) == set(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.shape == b.shape and a.dtype == b.dtype, k
        assert a.tobytes() == b.tobytes(), k


def test_forest_is_the_parents_bit_for_bit(monkeypatch):
    """The order of the f32 partial sums over chunks is kept: the forest
    equals, array for array, the one the code before PR 28 grew
    (`87e70bc`, the fixture) and the one the `native` routing grows."""
    xla = _forest_with_small_chunks(monkeypatch, "xla")
    with np.load(FIXTURE) as parent:
        _assert_same_arrays(xla, dict(parent))
    from ydf_tpu.ops.routing_native import available

    if available():
        _assert_same_arrays(
            _forest_with_small_chunks(monkeypatch, "native"), xla)


# ------------------------------------------------ (c) the configuration's files


def test_manifest_holds_the_configuration_and_its_cell():
    m = manifest.load()
    assert len(m["configs"]) == 3 and len(m["workloads"]) == 3  # PR 36
    assert len(m["per_layer"]) == 18
    assert m["per_layer"][12]["name"] == "program_build_s"
    assert [e["workloads"] for e in m["per_layer"][13:15]] == [
        ["mslr30k_rank.sweep"]] * 2
    # PR 38: set-up's parts, in every cell
    assert [(e["name"], e["moves"], "workloads" in e)
            for e in m["per_layer"][15:]] == [
        ("dataset_ingest_s", "setup_s", False),
        ("bin_build_s", "setup_s", False),
        ("inputs_build_s", "setup_s", False)]
    cell, entry, cfg, mix, limits = manifest.cell_files(m, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "sweep")
    assert entry["reduced"] == ["num_trees", "rows"]
    assert cfg["published"] == {
        "rows": 11_000_000, "features": 28, "num_trees": 500,
        "max_depth": 6, "shrinkage": 0.1, "task": "classification"}
    hp = cfg["hyperparameters"]
    assert (cfg["features"], cfg["table"], cfg["hist_quant"]) == (
        28, "binary_logit", "f32")
    assert hp["task"] == "CLASSIFICATION" and hp["use_hessian_gain"] is True
    assert (hp["max_depth"], hp["num_bins"], hp["shrinkage"]) == (6, 256, 0.1)
    assert cfg["reference"]["loss"] == "binomial"
    assert cfg["rows"] % 1_000_000 == 0 and cfg["rows"] > 11_000_000
    for exact in ("jobs_differ", "bin_edges_differ", "thresholds_off_grid",
                  "leaf_rows_gap", "programs_built_in_window"):
        assert limits[exact] == 0


def test_use_hessian_gain_is_a_hyperparameter_and_false_is_refused():
    spec = ydf.GradientBoostedTreesLearner.hyperparameter_spec()
    assert spec["use_hessian_gain"].default is True
    learner = ydf.GradientBoostedTreesLearner(label="y",
                                              use_hessian_gain=True)
    assert learner.hyperparameters()["use_hessian_gain"] is True
    with pytest.raises(NotImplementedError, match="hessian gain"):
        ydf.GradientBoostedTreesLearner(label="y", use_hessian_gain=False)


# ------------------------------------------- (d) what the program's build cost


@pytest.mark.parametrize("driver", ["single_scan", "early_stop"])
def test_program_build_seconds_are_repeated_not_added(driver):
    kw = {"single_scan": {"num_trees": 4},
          "early_stop": {"num_trees": 12,
                         "early_stopping_num_trees_look_ahead": 3}}[driver]
    rng = np.random.RandomState(5)
    data = {f"x{i}": rng.normal(size=1777).astype(np.float32)
            for i in range(3)}
    data["y"] = (data["x0"] - data["x1"]
                 + 0.1 * rng.normal(size=1777)).astype(np.float32)

    def profile():
        return ydf.GradientBoostedTreesLearner(
            label="y", task=Task.REGRESSION, max_depth=3, random_seed=28,
            **kw).train(data).training_profile

    first, second = profile(), profile()
    built = first["device_loop.program_build_s"]
    assert built > 0
    assert built == pytest.approx(first["device_loop.compile"], rel=1e-9)
    assert first["device_loop.compile"] <= first["device_loop.dispatch"]
    assert second["device_loop.compile"] == 0.0
    assert second["device_loop.program_build_s"] == built
    assert second["device_loop.program_from_cache"] == 0.0

    from metrics import program_build_s

    assert program_build_s.read(
        {"jobs": [{"profile": second}, {"profile": first}]}) == built
    assert program_build_s.read({"jobs": [{"profile": {}}]}) is None


# ------------------------------------------ (e) nodes of 2**24 rows and more


def _decide_one_node(counts, cut, own_cells=True, g_of=None, rounding=None):
    """The grower's decision for one node whose one feature holds
    `counts` rows a bin, gradients of one sign up to bin `cut` and of
    the other past it, so that `cut` is the best split. `own_cells`
    False is the form before PR 28: every right side `parent - left`."""
    import jax
    import jax.numpy as jnp

    from ydf_tpu.ops import grower
    from ydf_tpu.ops.split_rules import HessianGainRule

    B = len(counts)
    n = jnp.asarray(counts, jnp.float32)
    g = jnp.where(jnp.arange(B) <= cut, n, -n) if g_of is None else g_of(n)
    hist = jnp.stack([g, n, n], axis=-1)[None, None]  # [1, 1, B, 3]
    parent = jnp.sum(hist[0, 0], axis=0)[None]  # [1, 3], as f32 adds it
    if rounding is not None:  # what another order of adding leaves
        parent = parent + jnp.asarray(rounding, jnp.float32)
    rule = HessianGainRule(l2=0.0)
    left_all, ranks, right_scalar = grower.scalar_candidates(
        hist, Fn=1, O=1, rule=rule, rule_ctx=None)
    key = jax.random.PRNGKey(0)
    return grower.layer_decide(
        left_all, ranks, None, parent, jnp.asarray([True]),
        jnp.asarray([0], jnp.int32), jnp.asarray(1, jnp.int32), key, key,
        None, None, rule=rule, L=2, B=B, N=3, Fn=1, Fc=0, O=1, Fs=0,
        W=(B + 31) // 32, min_examples=5, min_split_gain=0.0,
        candidate_features=-1, num_valid_features=None,
        children_in_frontier=True,
        right_scalar=right_scalar if own_cells else None)


def test_right_child_of_a_large_node_is_summed_from_its_own_cells():
    """f32 holds every integer below 2**24: a child below it is counted
    exactly even where its parent and its sibling are past it (at 40M
    rows `parent - left` left 2 of 64 leaf counts off by 2)."""
    rng = np.random.RandomState(400_003)
    counts = [400_003 + int(rng.randint(0, 1000)) for _ in range(64)]
    left, right = sum(counts[:46]), sum(counts[46:])
    assert left > 1 << 24 > right and left % 2 == 1
    old = _decide_one_node(counts, cut=45, own_cells=False)
    new = _decide_one_node(counts, cut=45)
    assert int(new.best_t[0]) == int(old.best_t[0]) == 45
    assert float(new.right_stats[0, -1]) == right
    assert float(old.right_stats[0, -1]) != right  # what it cures
    assert abs(float(new.left_stats[0, -1]) - left) <= 1  # odd: no f32
    np.testing.assert_array_equal(np.asarray(new.left_stats),
                                  np.asarray(old.left_stats))


def test_empty_side_of_a_large_node_is_no_split():
    """A node past 2**24 rows whose total was added in another order than
    the prefix sums of a feature differs from them by a few rows of
    rounding. Where no row lies past a cut, `parent - left` then reads
    those few rows, passes min_examples and wins on g^2 / h of two
    residues (a leaf of 2.6e11 on the chip). The cells past the cut sum
    to exactly nothing."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    counts = [600_001 + int(rng.randint(0, 1000)) for _ in range(40)]
    counts += [0] * 24  # an ancestor's split emptied the upper bins
    noise = jnp.asarray(rng.normal(size=64) * 1e-6, jnp.float32)
    kw = dict(cut=20, g_of=lambda n: n * noise,
              rounding=[0.37, 1e-4, 8.0])
    old = _decide_one_node(counts, own_cells=False, **kw)
    assert int(old.best_t[0]) >= 39 and float(old.right_stats[0, -1]) == 8
    new = _decide_one_node(counts, **kw)
    assert int(new.best_t[0]) < 39  # never a cut with nothing past it
    assert float(new.right_stats[0, -1]) >= 600_001


def test_nodes_below_2_to_the_24_keep_their_bits():
    counts = [100_001.5] * 64  # weighted rows: 6.4M, below 2**24
    old = _decide_one_node(counts, cut=20, own_cells=False)
    new = _decide_one_node(counts, cut=20)
    for a, b in zip(old, new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------- (f) the classification label path on the host


def test_retyped_dataset_is_kept_with_the_dataset_it_came_from():
    """A Dataset ingested without a learner holds its whole-number label
    as NUMERICAL; classification forces it CATEGORICAL. The re-inferred
    Dataset, with its fitted binner and bins, is made once, not a job."""
    rng = np.random.RandomState(3)
    cols = {"x": rng.normal(size=500).astype(np.float32),
            "label": rng.randint(0, 2, size=500)}
    ds = ydf.Dataset.from_data(cols, label="label")
    learner = ydf.GradientBoostedTreesLearner(label="label", num_trees=2)
    first = learner._infer_dataset(ds)
    assert first is not ds and first is learner._infer_dataset(ds)
    assert learner._prepare(ds)["binner"] is learner._prepare(ds)["binner"]
    other = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=2, min_vocab_frequency=1)
    assert other._infer_dataset(ds) is not first  # another dictionary


@pytest.mark.parametrize("values, dtype", [
    ([-3, 0, 1, 7, 40_000], np.int64),
    # -100..100 in int8: `raw - lo` is 200, which wraps to -56 in int8
    ([-100, -1, 0, 50, 100], np.int8),
    ([-30_000, 0, 5, 20_000, 30_000], np.int16),
])
def test_whole_number_labels_encode_as_the_sorted_path_does(values, dtype):
    from ydf_tpu.dataset.dataspec import ColumnType

    rng = np.random.RandomState(4)
    ints = rng.choice(values, size=4000,
                      p=[.3, .3, .2, .19, .01]).astype(dtype)
    forced = {"label": ColumnType.CATEGORICAL}
    as_int = ydf.Dataset.from_data({"label": ints}, column_types=forced,
                                   min_vocab_frequency=50)
    as_float = ydf.Dataset(  # float64 takes np.unique's path
        {"label": ints.astype(np.float64)}, as_int.dataspec)
    got = as_int.encoded_categorical("label")
    np.testing.assert_array_equal(got, as_float.encoded_categorical("label"))
    assert got.dtype == np.int32 and (got == 0).any()  # the last: too rare


def test_row_split_is_kept_with_the_dataset():
    """The seeded split's permutation and gathers are made once a
    Dataset, seed and ratio; the forests of a sweep stay bit-identical
    and another seed still splits anew. A job whose device inputs were
    kept (the second) splits nothing at all; one that comes back after
    another seed took the device's place (the fourth) finds the host's
    rows of the first."""
    from ydf_tpu.learners import gbt

    x, y = make_table(6000, 28, 13, "binary_logit")
    ds = ydf.Dataset.from_data(as_columns(x, y), label="label")

    def train(**kw):
        return ydf.GradientBoostedTreesLearner(
            label="label", num_trees=2, max_depth=3, **kw).train(ds)

    calls = []
    real = gbt._split_rows

    def counted(dataset, bins_all, rng, seed, ratio):
        rows = real(dataset, bins_all, rng, seed, ratio)
        calls.append(rows[2])
        return rows

    gbt._split_rows = counted
    try:
        first, second, other, back = (
            train(), train(), train(random_seed=5), train())
    finally:
        gbt._split_rows = real
    assert len(calls) == 3  # first, other, back: not the second
    assert calls[2] is calls[0] and calls[1] is not calls[0]
    assert not calls[0].flags.writeable
    # what it costs is on the memory ledger: the bins once more a seed
    assert ds._retyped and all(
        d.bin_cache_bytes() >= 3 * calls[0].nbytes
        for d in ds._retyped.values())
    a, b, c, d = (m.forest.to_numpy() for m in (first, second, other, back))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(d[k]))
    assert not np.array_equal(np.asarray(a["threshold"]),
                              np.asarray(c["threshold"]))
