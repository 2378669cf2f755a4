"""Loads BENCHMARK.json with every file it names, and checks all of it
against the driver's rules for names, before a chip is asked for.

    python3 benchmark/harness/manifest.py        # the self-check

A cell is found by name: its configuration at `configs/<config>.json`
(the manifest's `file`), its traffic mix at `traffic/<traffic>.json`,
its limits at `limits/<cell>.json`, and each per-layer metric's reader
at `metrics/<metric>.py`. What a configuration's file names is found by
name too: a `table` kind that `harness/datagen.py` does not make itself
at `tables/<table>.py`, its `reference` block's `module` at
`references/<module>.py`, its `learner` among the program's learners,
and a cell's `chips` reach that learner as a mesh over them
(`harness/runner.py` `learner_of`). Adding any of them is adding files
and entries; nothing here lists them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per")


class ManifestError(ValueError):
    pass


def _line(text, what, limit=200):
    if (not isinstance(text, str) or not 1 <= len(text) <= limit
            or "\n" in text or "\t" in text):
        raise ManifestError(f"{what}: 1 to {limit} characters on one line")


def _name(text, what):
    if not isinstance(text, str) or not NAME.match(text):
        raise ManifestError(
            f"{what} {text!r}: 1 to 64 letters, digits, '_', '.', '-', "
            "starting with a letter, digit or '_'")


def _keys(entry, required, optional, what):
    extra = set(entry) - required - optional
    missing = required - set(entry)
    if extra or missing:
        raise ManifestError(f"{what}: extra keys {sorted(extra)}, "
                            f"missing keys {sorted(missing)}")


def load_json(relpath):
    with open(os.path.join(ROOT, relpath)) as f:
        return json.load(f)


def load(check_files=True):
    """The manifest as a dict, checked. Raises ManifestError."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        raise ManifestError("BENCHMARK.json is over 64 KiB")
    m = load_json("BENCHMARK.json")
    if set(m) != TOP_KEYS:
        raise ManifestError(f"top-level keys must be {sorted(TOP_KEYS)}")
    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32):
        raise ManifestError("command: a list of 1 to 32 strings")
    for word in m["command"]:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command word {word!r} leaves the repo")
    if not 1 <= len(m["paths"]) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise ManifestError(f"path {p!r}")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        raise ManifestError("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in m["paths"])

    configs, files = {}, set()
    if not 1 <= len(m["configs"]) <= 24:
        raise ManifestError("configs: 1 to 24")
    for c in m["configs"]:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(), "config")
        _name(c["name"], "config name")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if c["name"] in configs or c["file"] in files:
            raise ManifestError(f"config {c['name']!r} or its file twice")
        if not PATH.match(c["file"]) or not under_paths(c["file"]):
            raise ManifestError(f"config file {c['file']!r} not under paths")
        if len(c["reduced"]) > 16:
            raise ManifestError("reduced: at most 16 keys")
        for key in c["reduced"]:
            _name(key, "reduced key")
            if (key.endswith(("_dim", "_rank", "_size"))
                    or any(w in key for w in WIDTH_WORDS)):
                raise ManifestError(f"reduced names a width: {key!r}")
        configs[c["name"]] = c
        files.add(c["file"])

    cells, pairs = {}, set()
    if not 1 <= len(m["workloads"]) <= 24:
        raise ManifestError("workloads: 1 to 24")
    for w in m["workloads"]:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(), "cell")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"cell {k}")
        _line(w["why"], "cell why")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips is 1 or 4")
        if w["config"] not in configs:
            raise ManifestError(f"cell {w['name']}: unknown config")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"cell {w['name']!r} or its pair twice")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
    unused = set(configs) - {w["config"] for w in cells.values()}
    if unused:
        raise ManifestError(f"configs used by no cell: {sorted(unused)}")
    four = sum(w["chips"] == 4 for w in cells.values())
    if four > max(1, len(cells) // 4):
        raise ManifestError("too many four-chip cells")

    metrics = {}

    def metric(e, per_layer):
        required = {"name", "unit", "better", "source"}
        required |= {"layer", "moves"} if per_layer else {"bound"}
        _keys(e, required, {"workloads"}, f"metric {e.get('name')}")
        _name(e["name"], "metric name")
        if not UNIT.match(e["unit"]):
            raise ManifestError(f"metric {e['name']}: unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            raise ManifestError(f"metric {e['name']}: better")
        allowed = SOURCES if per_layer else {"host_clock", "device_trace"}
        if e["source"] not in allowed:
            raise ManifestError(f"metric {e['name']}: source {e['source']!r}")
        if e["name"] in metrics:
            raise ManifestError(f"metric {e['name']!r} twice")
        for w in e.get("workloads", []):
            if w not in cells:
                raise ManifestError(f"metric {e['name']}: unknown cell {w!r}")
        metrics[e["name"]] = e

    if not 1 <= len(m["end_to_end"]) <= 16:
        raise ManifestError("end_to_end: 1 to 16")
    for e in m["end_to_end"]:
        metric(e, per_layer=False)
        if not (isinstance(e["bound"], (int, float)) and 0 < e["bound"] <= 0.1):
            raise ManifestError(f"metric {e['name']}: bound in (0, 0.1]")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        raise ManifestError("setup_s has to be an end-to-end metric of "
                            "every cell")
    if not 1 <= len(m["per_layer"]) <= 128:
        raise ManifestError("per_layer: 1 to 128")

    def reports(cell, e):
        return "workloads" not in e or cell in e["workloads"]

    for e in m["per_layer"]:
        metric(e, per_layer=True)
        _name(e["layer"], "layer")
        if e["moves"] not in e2e:
            raise ManifestError(f"metric {e['name']}: moves {e['moves']!r} "
                                "is no end-to-end metric")
        for cell in e.get("workloads", cells):
            if not reports(cell, e2e[e["moves"]]):
                raise ManifestError(
                    f"metric {e['name']}: cell {cell} does not report "
                    f"{e['moves']}")
        if ("roofline" in e["name"] or "mfu" in e["name"]) and e["unit"] != "%":
            raise ManifestError(f"metric {e['name']}: a share has unit %")
    for cell in cells:
        mine = [e for e in m["end_to_end"] if reports(cell, e)]
        if len(mine) < 2:
            raise ManifestError(f"cell {cell}: needs setup_s and one more")
        if not any(reports(cell, e) and reports(cell, e2e[e["moves"]])
                   for e in m["per_layer"]):
            raise ManifestError(f"cell {cell}: no per-layer metric")

    if check_files:
        for c in configs.values():
            cfg = load_json(c["file"])
            for key in c["reduced"]:
                if key not in cfg:
                    raise ManifestError(
                        f"config {c['name']}: reduced key {key!r} not in file")
            config_names(cfg, c["name"])
        for w in cells.values():
            traffic_file(w["traffic"])
            load_json(f"benchmark/limits/{w['name']}.json")
        for e in m["per_layer"]:
            reader(e["name"])
            meta = sys.modules["metrics." + e["name"]].META
            said = {k: e.get(k) for k in meta}
            if meta != said:
                raise ManifestError(
                    f"metric {e['name']}: its file says {meta}, "
                    f"BENCHMARK.json says {said}")
    return m


def traffic_file(traffic):
    base = os.path.join(BENCH, "traffic", traffic)
    for suffix in TRAFFIC_SUFFIXES:
        if os.path.exists(base + suffix):
            return base + suffix
    raise ManifestError(f"no traffic file for {traffic!r} under traffic/")


def named_module(directory, name, gives):
    """The module `<directory>/<name>.py` under the benchmark, which has
    to define every name in `gives`. It is loaded by its path, so that no
    installed package of the directory's name stands in its way."""
    _name(name, f"{directory} file")
    folder = os.path.join(BENCH, directory)
    path = os.path.join(folder, name + ".py")
    if not os.path.exists(path):
        known = sorted(f[:-3] for f in os.listdir(folder)
                       if f.endswith(".py")) if os.path.isdir(folder) else []
        raise ManifestError(f"no benchmark/{directory}/{name}.py; "
                            f"known: {known}")
    key = f"benchmark_{directory}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[key] = module
    module = sys.modules[key]
    missing = [g for g in gives if not callable(getattr(module, g, None))]
    if missing:
        raise ManifestError(f"benchmark/{directory}/{name}.py lacks {missing}")
    return module


def config_names(config, config_name):
    """Checks that what a configuration's file names by name is there:
    its table's file, its reference's module, a well-formed learner (the
    learner itself is looked up in the program, which this check does not
    import)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import datagen

    try:
        if config["table"] not in datagen.KINDS:
            named_module("tables", config["table"], ("make_table",))
        if "module" in config["reference"]:
            named_module("references", config["reference"]["module"],
                         ("forest_arrays", "readings"))
    except ManifestError as err:
        raise ManifestError(f"config {config_name}: harness/datagen.py makes "
                            f"{datagen.KINDS}, harness/reference.py reads "
                            f"where no module is named, and {err}")
    _name(config.get("learner", "GradientBoostedTreesLearner"),
          f"config {config_name}: learner")


def reader(metric_name):
    """The `read(run)` function of a per-layer metric's own file."""
    path = os.path.join(BENCH, "metrics", metric_name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no reader {path}")
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("metrics." + metric_name).read


def cell_files(m, cell_name):
    """(cell, config entry, config, traffic mix, limits) of one cell."""
    cells = {w["name"]: w for w in m["workloads"]}
    if cell_name not in cells:
        raise ManifestError(f"unknown workload {cell_name!r}; "
                            f"known: {sorted(cells)}")
    cell = cells[cell_name]
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    path = traffic_file(cell["traffic"])
    if not path.endswith(".json"):
        raise ManifestError("the traffic generator reads .json mixes")
    with open(path) as f:
        traffic = json.load(f)
    return (cell, entry, load_json(entry["file"]), traffic,
            load_json(f"benchmark/limits/{cell_name}.json"))


def metrics_of(m, cell_name, per_layer):
    return [e for e in m["per_layer" if per_layer else "end_to_end"]
            if "workloads" not in e or cell_name in e["workloads"]]


if __name__ == "__main__":
    try:
        checked = load()
    except (ManifestError, OSError, json.JSONDecodeError) as err:
        sys.exit(f"manifest self-check FAILED: {err}")
    print(f"manifest self-check passed: {len(checked['workloads'])} cells, "
          f"{len(checked['configs'])} configurations, "
          f"{len(checked['end_to_end'])} end-to-end and "
          f"{len(checked['per_layer'])} per-layer metrics")
