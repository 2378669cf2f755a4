"""The manifest self-check passes on the committed files and refuses the
faults the driver refused in earlier attempts; what a configuration's
file names (a table kind, a reference module, a learner) is found by
name, and an unknown name is refused with the names that are known."""

import copy
import json
import os
import subprocess
import sys

import pytest

import ydf_tpu
from harness import compare, datagen, manifest, runner
from harness.datagen import make_table


def test_committed_manifest_passes():
    m = manifest.load()
    assert [w["name"] for w in m["workloads"]][0] == "synth100_gbt.sweep"


def test_self_check_runs_as_a_script():
    """`python3 benchmark/harness/manifest.py`, where `harness` is not
    importable until the check puts the benchmark on the path."""
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH, "harness", "manifest.py")],
        capture_output=True, text=True, timeout=120, cwd=manifest.ROOT)
    assert out.returncode == 0, out.stderr
    assert "self-check passed: 2 cells" in out.stdout


@pytest.fixture
def broken(monkeypatch):
    good = manifest.load_json("BENCHMARK.json")

    def load_with(change):
        m = copy.deepcopy(good)
        change(m)
        real = manifest.load_json
        monkeypatch.setattr(
            manifest, "load_json",
            lambda p: m if p == "BENCHMARK.json" else real(p))
        return manifest.load()

    return load_with


@pytest.mark.parametrize("change", [
    lambda m: m["per_layer"][0].update(layer="ingest + binning (host)"),
    lambda m: m["per_layer"][0].update(unit="tokens per second"),
    lambda m: m["per_layer"][0].update(moves="nothing"),
    lambda m: m["per_layer"][0].update(why="extra key"),
    lambda m: m["end_to_end"][0].update(bound=0.2),
    lambda m: m["workloads"][0].update(traffic="no_such_mix"),
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["configs"][0].update(reduced=["hidden_dim"]),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="twin")),
    lambda m: m.update(run_seconds=52),
    lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="no_reader")),
], ids=["layer_phrase", "unit_spaces", "moves_unknown", "extra_key",
        "bound_loose", "traffic_missing", "name_space", "width_reduced",
        "pair_twice", "run_seconds", "reader_missing"])
def test_faults_are_refused(broken, change):
    with pytest.raises(manifest.ManifestError):
        broken(change)


def test_result_line_names_match_manifest():
    m = manifest.load()
    for e in m["per_layer"]:
        assert callable(manifest.reader(e["name"]))
    json.dumps(m)


# ------------------------------------ what a configuration names, by name


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """`manifest.BENCH` moved to an empty directory to put stand-in files
    in; the modules loaded from it are forgotten afterwards."""
    monkeypatch.setattr(manifest, "BENCH", str(tmp_path))
    yield tmp_path
    for key in [k for k in sys.modules if k.startswith("benchmark_")]:
        del sys.modules[key]


def test_a_table_kind_is_a_file_of_its_own(bench_copy):
    (bench_copy / "tables").mkdir()
    (bench_copy / "tables" / "ones.py").write_text(
        "import numpy as np\n"
        "def make_table(rows, features, seed):\n"
        "    return (np.ones((features, rows), np.float32),\n"
        "            np.full(rows, seed, np.int64))\n")
    x, y = make_table(5, 3, 9, "ones")
    assert x.shape == (3, 5) and list(y) == [9] * 5
    with pytest.raises(manifest.ManifestError) as err:
        make_table(5, 3, 9, "twos")
    assert "'twos'" in str(err.value) and "binary_logit" in str(err.value)
    assert "['ones']" in str(err.value)


def test_a_reference_is_a_module_of_its_own(bench_copy):
    assert compare.of_config({"reference": {}}) is compare
    (bench_copy / "references").mkdir()
    (bench_copy / "references" / "stand_in.py").write_text(
        "def forest_arrays(model):\n    return {'model': model}\n"
        "def readings(x, y, hp, jobs, follow_trees=3, devices=None):\n"
        "    return {'jobs': len(jobs), 'devices': len(devices)}\n")
    (bench_copy / "references" / "half.py").write_text(
        "def forest_arrays(model):\n    return {}\n")
    module = compare.of_config({"reference": {"module": "stand_in"}})
    assert module.forest_arrays(3) == {"model": 3}
    assert module.readings(0, 0, {}, [1, 2], devices=[0]) == {
        "jobs": 2, "devices": 1}
    with pytest.raises(manifest.ManifestError) as err:
        compare.of_config({"reference": {"module": "absent"}})
    assert "absent" in str(err.value)
    assert "['half', 'stand_in']" in str(err.value)
    with pytest.raises(manifest.ManifestError, match="lacks .'readings'."):
        compare.of_config({"reference": {"module": "half"}})


def test_a_learner_is_looked_up_in_the_program():
    config = {"rows": 100, "features": 10, "table": "binary_logit",
              "hyperparameters": {"task": "CLASSIFICATION"}, "reference": {}}
    mix = {"loop": "closed", "clients": 1, "dataset": "fresh"}
    assert type(runner.Traffic(config, mix, 1).new_learner()) is (
        ydf_tpu.GradientBoostedTreesLearner)
    named = runner.Traffic(dict(config, learner="RandomForestLearner"), mix, 1)
    assert type(named.new_learner()) is ydf_tpu.RandomForestLearner
    with pytest.raises(ValueError) as err:
        runner.Traffic(dict(config, learner="NoSuchLearner"), mix, 1)
    assert "NoSuchLearner" in str(err.value)
    assert "GradientBoostedTreesLearner" in str(err.value)


@pytest.mark.parametrize("change,said", [
    (lambda c: c.update(table="no_such_table"), "no_such_table"),
    (lambda c: c["reference"].update(module="no_such_module"),
     "no_such_module"),
    (lambda c: c.update(learner="has space"), "learner"),
], ids=["table", "reference", "learner"])
def test_self_check_names_the_missing_file(monkeypatch, change, said):
    real = manifest.load_json

    def load_json(path):
        loaded = real(path)
        if path.endswith("configs/higgs_gbt.json"):
            change(loaded)
        return loaded

    monkeypatch.setattr(manifest, "load_json", load_json)
    with pytest.raises(manifest.ManifestError) as err:
        manifest.load()
    assert said in str(err.value) and "higgs_gbt" in str(err.value)


def test_kinds_made_here_need_no_file():
    assert datagen.KINDS == ("binary_logit", "linear_regression")
    manifest.load()  # both configurations name one of them
