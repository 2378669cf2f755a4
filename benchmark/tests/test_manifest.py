"""The manifest self-check passes on the committed files and refuses the
faults the driver refused in earlier attempts."""

import copy
import json

import pytest

from harness import manifest


def test_committed_manifest_passes():
    m = manifest.load()
    assert [w["name"] for w in m["workloads"]][0] == "synth100_gbt.sweep"


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
