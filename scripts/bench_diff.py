"""Bench regression sentinel: shape-paired diff of two bench rounds.

The r04→r05 lesson, made structural. ROADMAP once read "640 ns (r04) →
1381 ns (r05)" as a serving regression; a full PR of bisection showed a
SHAPE CONFOUND — 640.5 ns was r04's 5-tree quick-floor record, 1381 ns
r05's 20-tree full record, and same-shape serving had actually improved
5 %. Nothing in the repo could diff two `BENCH_r*.json` rounds, so
every cross-round comparison was an eyeball over raw JSON lines with
exactly that failure mode. This tool:

  * loads any two bench artifacts — a driver wrapper (`{"tail": ...}`
    holding the emitted JSON lines, the checked-in BENCH_r* format), a
    JSONL of records, or a single record object;
  * keeps only MEASURED headline records (projections and error records
    dropped) and pairs them **by record shape**
    `(metric, backend, rows, trees, depth, dist_mode, load_mode,
    fleet_replicas, hist/bin/route/serve_threads)` — records whose
    shape appears in only one round are listed as unpaired, NEVER
    diffed (the confound class is dead by construction); `load_mode`
    keeps serving-load artifacts (scripts/bench_serve_load.py) pairing
    closed-with-closed and open-with-open only, `fleet_replicas` keeps
    fleet rounds pairing at identical replica count, and the thread
    caps (defaulting to 1 when absent, matching the 1-core historical
    rounds) keep an N-core round from ever diffing against a 1-core
    one;
  * diffs every per-stage field two paired records share —
    `ingest_s`…`fused_s`, the serving latencies/QPS, the `dist_*`
    family, and the round-15 utilization/memory fields
    (`pool_utilization.*`, `train_peak_rss_bytes`, `serve_bank_bytes`,
    `dist_shard_bytes`, `infer_peak_rss_delta_bytes`) — against
    per-field noise thresholds (relative + absolute floor, direction
    aware), emitting verdicts `regression` / `improvement` /
    `unchanged` (fields without a spec are reported `info`-only);
  * writes a markdown report and a JSON verdict.

Usage:

    python scripts/bench_diff.py BENCH_r04.json BENCH_r05.json
    python scripts/bench_diff.py A B --json out.json --md out.md \
        --fail-on-regression

Exit code 0 normally; with `--fail-on-regression`, 1 when any paired
field regressed past its threshold. tests/test_bench_diff.py runs this
over the checked-in r04/r05 rounds (asserting the 640 ns confound is
NOT flagged) and over a synthetically injected per-stage regression
(asserting it IS) in tier-1. docs/observability.md "Reading a bench
diff" walks the real r04→r05 output.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

#: Shape key: records are only comparable at identical workload shape.
#: dist_mode joins the key so a row-parallel round can never be diffed
#: against a feature-parallel one (their dist_* fields measure
#: different exchanges — protocol bytes, merge domains, shard
#: residency); load_mode joins it so a serving-load artifact's
#: closed-loop capacity run never pairs with an open-loop latency run
#: (scripts/bench_serve_load.py emits both per round); fleet_replicas
#: joins it so a 2-replica fleet round never pairs with a 4-replica one
#: (per-replica QPS scales with the pool — comparing across counts is
#: the same confound class). Records without those families carry
#: neither key and pair exactly as before. The four kernel thread caps
#: join the key in the many-core round: a 1-core r01–r05 record must
#: never cross-compare with an N-core r06 one (every per-stage wall
#: scales with the pool — the exact confound class again). They DEFAULT
#: TO 1 when absent so the historical records, all measured on the
#: 1-core box before the fields existed, keep pairing with each other
#: and with explicit single-threaded rounds.
THREAD_SHAPE_FIELDS = ("hist_threads", "bin_threads", "route_threads",
                       "serve_threads")
#: `fleet_elastic` DEFAULTS TO 0 when absent: an elastic fleet record
#: (the closed loop spans a live add_replica/remove_replica —
#: YDF_TPU_BENCH_FLEET_ELASTIC) must never pair with a static one (the
#: scale ops perturb the run's tail and capacity), and every historical
#: fleet record predates the mode, i.e. was static.
LOOP_SHAPE_FIELDS = ("fleet_elastic",)
SHAPE_FIELDS = ("metric", "backend", "rows", "trees", "depth",
                "dist_mode", "load_mode",
                "fleet_replicas") + THREAD_SHAPE_FIELDS \
    + LOOP_SHAPE_FIELDS

#: field (or dotted-prefix, trailing ".") -> (direction, rel_noise,
#: abs_floor). direction "lower" = smaller is better. A change is a
#: regression/improvement only when it moves past BOTH the relative
#: noise band and the absolute floor; otherwise "unchanged".
FIELD_SPECS: Dict[str, Tuple[str, float, float]] = {
    "value": ("higher", 0.10, 0.0),
    "vs_baseline": ("higher", 0.10, 0.0),
    "train_wall_s": ("lower", 0.10, 0.2),
    "train_wall_incl_compile_s": ("lower", 0.15, 0.5),
    "ingest_s": ("lower", 0.20, 0.1),
    "bin_s": ("lower", 0.20, 0.1),
    "hist_s": ("lower", 0.15, 0.1),
    "hist_attrib_s": ("lower", 0.20, 0.1),
    "hist_direct_s": ("lower", 0.20, 0.1),
    "route_s": ("lower", 0.20, 0.05),
    "update_s": ("lower", 0.20, 0.05),
    "fused_s": ("lower", 0.15, 0.1),
    # Device-resident boosting loop (ops/device_loop.py accounting
    # around the steady train): fewer XLA dispatches and fewer
    # host-materialized bytes per tree are better. dispatches_per_tree
    # is a deterministic count (noise band only absorbs chunk-tail
    # rounding); host_sync is byte-exact per shape, the floor absorbs
    # dtype-width churn.
    "dispatches_per_tree": ("lower", 0.10, 0.01),
    "host_sync_bytes_per_tree": ("lower", 0.10, 1024.0),
    "infer_ns_per_example": ("lower", 0.10, 30.0),
    "infer_p50_ns": ("lower", 0.10, 30.0),
    "infer_p99_ns": ("lower", 0.15, 60.0),
    "infer_qps": ("higher", 0.10, 0.0),
    "infer_peak_rss_delta_bytes": ("lower", 0.25, float(1 << 20)),
    "train_peak_rss_bytes": ("lower", 0.10, float(64 << 20)),
    "serve_bank_bytes": ("lower", 0.10, float(1 << 20)),
    "dist_shard_bytes": ("lower", 0.10, float(1 << 20)),
    "dist_shard_bytes_per_worker": ("lower", 0.10, float(1 << 20)),
    "dist_shard_rows": ("lower", 0.05, 1024.0),
    "dist_merge_s": ("lower", 0.25, 0.05),
    # Manager tree-boundary snapshot wall (preemption-safe round):
    # fsync-dominated, so a generous rel band with a small abs floor.
    "dist_snapshot_s": ("lower", 0.30, 0.02),
    "dist_train_s": ("lower", 0.15, 0.2),
    "dist_compute_s": ("lower", 0.20, 0.1),
    "dist_net_s": ("lower", 0.25, 0.1),
    "dist_wait_s": ("lower", 0.25, 0.1),
    "dist_layer_wall_s": ("lower", 0.15, 0.2),
    "dist_reduce_bytes": ("lower", 0.05, 1024.0),
    # serving-under-load family (bench.py measure_serving_load_family /
    # scripts/bench_serve_load.py): capacity up is good, tail latency /
    # queue age / shed rate down is good.
    "serve_sustained_qps": ("higher", 0.15, 0.0),
    "serve_load_p50_ns": ("lower", 0.15, 100.0),
    "serve_load_p99_ns": ("lower", 0.25, 500.0),
    "serve_queue_age_p99_ns": ("lower", 0.25, 500.0),
    "serve_shed_rate": ("lower", 0.10, 0.01),
    # serving-fleet family (bench.py measure_fleet_family): sustained
    # capacity through the replica router up is good; the p99 of the
    # run spanning the hot-swap and the failover count down is good
    # (fleet_replicas itself is a SHAPE field, never diffed).
    "fleet_sustained_qps": ("higher", 0.15, 0.0),
    "fleet_swap_p99_ns": ("lower", 0.25, 500.0),
    "fleet_failover_count": ("lower", 0.50, 0.5),
    # elastic-membership additions (YDF_TPU_BENCH_FLEET_ELASTIC=1;
    # fleet_elastic itself is a SHAPE field, never diffed): faster
    # joins/drains are better, fewer scale events for the same run are
    # better (an autoscaler that flaps is a regression).
    "fleet_join_to_serving_ns": ("lower", 0.25, 500.0),
    "fleet_drain_ns": ("lower", 0.25, 500.0),
    "fleet_scale_events": ("lower", 0.50, 0.5),
    # transport-overhaul family (persistent pool + pipelining +
    # zero-copy framing): fewer connects and less wire traffic are
    # better, a higher connection-reuse rate is better, and the
    # per-RPC predict round-trip p50 is the protocol-overhead
    # instrument itself. The fleet family carries them bare; the
    # distributed family mirrors them under the dist_ prefix.
    "rpc_connects": ("lower", 0.25, 0.5),
    "rpc_conn_reuse_rate": ("higher", 0.05, 0.02),
    "rpc_header_bytes": ("lower", 0.15, 4096.0),
    "rpc_payload_bytes": ("lower", 0.10, 4096.0),
    "fleet_predict_rtt_p50_ns": ("lower", 0.20, 300.0),
    "dist_rpc_connects": ("lower", 0.25, 0.5),
    "dist_rpc_conn_reuse_rate": ("higher", 0.05, 0.02),
    "dist_rpc_header_bytes": ("lower", 0.15, 4096.0),
    "dist_rpc_payload_bytes": ("lower", 0.10, 4096.0),
    # loadgen artifact records (load_mode in the pairing shape)
    "achieved_qps": ("higher", 0.15, 0.0),
    "latency_p50_ns": ("lower", 0.15, 100.0),
    "latency_p99_ns": ("lower", 0.25, 500.0),
    "queue_age_p99_ns": ("lower", 0.25, 500.0),
    "serve_batcher_peak_bytes": ("lower", 0.25, float(1 << 16)),
    # cache-build family (bench.py measure_cache_build_family, env
    # YDF_TPU_BENCH_CACHE_WORKERS): build walls and the streaming
    # ingest's peak RSS down is good; sketch_bytes is the per-partial
    # wire cost of sketch-mode boundary inference, also lower-better.
    "cache_build_s": ("lower", 0.20, 0.1),
    "dist_cache_build_s": ("lower", 0.20, 0.1),
    "cache_build_peak_rss_bytes": ("lower", 0.15, float(64 << 20)),
    "sketch_bytes": ("lower", 0.10, 4096.0),
    "dist_cache_peak_worker_build_bytes": ("lower", 0.15, float(1 << 20)),
    "sketch_rank_error": ("lower", 0.50, 0.002),
    "sketch_split_max_drift": ("lower", 0.50, 0.002),
    # dotted-prefix rules (nested numeric dicts flatten to parent.key)
    "pool_utilization.": ("higher", 0.10, 0.05),
    # core-scaling family (bench.py measure_core_scaling, many-core
    # round): speedup and efficiency at the top core count up is good;
    # engaged_utilization (busy over the lanes a run actually engaged)
    # dropping means the steal schedule stopped covering stragglers.
    "scaling_speedup.": ("higher", 0.10, 0.05),
    "parallel_efficiency.": ("higher", 0.10, 0.05),
    "engaged_utilization.": ("higher", 0.10, 0.05),
    "infer_batch_p50_ns.": ("lower", 0.15, 100.0),
    "infer_batch_p99_ns.": ("lower", 0.20, 200.0),
    "dist_rpc_p50_ns.": ("lower", 0.25, 1000.0),
}


def load_records(path: str) -> List[dict]:
    """All measured headline records in `path`, in emission order.
    Accepts the driver wrapper ({"tail": <stdout lines>}), a JSONL
    stream, or one record object."""
    with open(path) as f:
        text = f.read()
    records: List[dict] = []

    def _maybe_add(obj) -> None:
        if not isinstance(obj, dict):
            return
        metric = obj.get("metric")
        if not isinstance(metric, str):
            return
        if metric.endswith("_PROJECTED"):
            return  # analytic projection, not a measurement
        if obj.get("backend") == "analytic_projection":
            return
        if "value" not in obj:
            return
        if obj.get("value") in (0, 0.0) and "error" in obj:
            return  # structured failure record, nothing to compare
        records.append(obj)

    stripped = text.strip()
    parsed = None
    if stripped.startswith("{"):
        try:
            parsed = json.loads(stripped)
        except ValueError:
            parsed = None
    if isinstance(parsed, dict) and "tail" in parsed and isinstance(
        parsed["tail"], str
    ):
        # Driver wrapper: the emitted JSON lines live in "tail".
        for line in parsed["tail"].splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    _maybe_add(json.loads(line))
                except ValueError:
                    continue
        return records
    if isinstance(parsed, dict):
        _maybe_add(parsed)
        return records
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                _maybe_add(json.loads(line))
            except ValueError:
                continue
    return records


def shape_key(rec: dict) -> Tuple:
    return tuple(
        rec.get(k, 1) if k in THREAD_SHAPE_FIELDS
        else rec.get(k, 0) if k in LOOP_SHAPE_FIELDS
        else rec.get(k)
        for k in SHAPE_FIELDS
    )


def shape_str(key: Tuple) -> str:
    # Thread caps at their default (1) and the elastic-fleet mode at
    # its default (0 = static) stay out of the label: every historical
    # record would otherwise carry the noise terms.
    return ", ".join(
        f"{name}={val}" for name, val in zip(SHAPE_FIELDS, key)
        if val is not None
        and not (name in THREAD_SHAPE_FIELDS and val == 1)
        and not (name in LOOP_SHAPE_FIELDS and val == 0)
    )


def flatten_numeric(rec: dict) -> Dict[str, float]:
    """Numeric fields of one record, one level of nested dicts flattened
    to dotted names (pool_utilization.hist, infer_batch_p50_ns.256)."""
    out: Dict[str, float] = {}
    for k, v in rec.items():
        if k in SHAPE_FIELDS:
            continue
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[k] = float(v)
        elif isinstance(v, dict):
            for sk, sv in v.items():
                if isinstance(sv, bool):
                    continue
                if isinstance(sv, (int, float)):
                    out[f"{k}.{sk}"] = float(sv)
    return out


def field_spec(name: str) -> Optional[Tuple[str, float, float]]:
    spec = FIELD_SPECS.get(name)
    if spec is not None:
        return spec
    dot = name.find(".")
    if dot >= 0:
        return FIELD_SPECS.get(name[: dot + 1])
    return None


def diff_fields(
    a: Dict[str, float], b: Dict[str, float]
) -> Dict[str, dict]:
    """Per-field verdicts for two flattened, SAME-SHAPE records."""
    out: Dict[str, dict] = {}
    for name in sorted(set(a) & set(b)):
        va, vb = a[name], b[name]
        delta = vb - va
        rel = delta / abs(va) if va else (0.0 if not delta else float("inf"))
        entry = {
            "a": va,
            "b": vb,
            "delta": round(delta, 6),
            "rel": round(rel, 4) if rel != float("inf") else None,
        }
        spec = field_spec(name)
        if spec is None:
            entry["verdict"] = "info"
        else:
            direction, rel_noise, abs_floor = spec
            # Signed "badness": positive = moved the bad way.
            bad = delta if direction == "lower" else -delta
            over_noise = abs(delta) > abs_floor and (
                va == 0 or abs(delta) > rel_noise * abs(va)
            )
            if not over_noise:
                entry["verdict"] = "unchanged"
            elif bad > 0:
                entry["verdict"] = "regression"
            else:
                entry["verdict"] = "improvement"
        out[name] = entry
    return out


def diff(path_a: str, path_b: str) -> dict:
    """The full verdict document for two bench artifacts."""
    recs_a, recs_b = load_records(path_a), load_records(path_b)
    # Last record per shape wins: the bench emits progressively better
    # floors, and the consumer protocol already takes the last line.
    by_shape_a = {shape_key(r): r for r in recs_a}
    by_shape_b = {shape_key(r): r for r in recs_b}
    shared = [k for k in by_shape_a if k in by_shape_b]
    pairs = []
    regressions: List[str] = []
    improvements: List[str] = []
    for key in shared:
        fields = diff_fields(
            flatten_numeric(by_shape_a[key]),
            flatten_numeric(by_shape_b[key]),
        )
        pair_reg = [n for n, e in fields.items()
                    if e["verdict"] == "regression"]
        pair_imp = [n for n, e in fields.items()
                    if e["verdict"] == "improvement"]
        regressions += [f"{shape_str(key)} :: {n}" for n in pair_reg]
        improvements += [f"{shape_str(key)} :: {n}" for n in pair_imp]
        pairs.append({
            "shape": dict(zip(SHAPE_FIELDS, key)),
            "fields": fields,
            "regressions": pair_reg,
            "improvements": pair_imp,
        })
    return {
        "a": path_a,
        "b": path_b,
        "records_a": len(recs_a),
        "records_b": len(recs_b),
        "pairs": pairs,
        "unpaired_a": [
            shape_str(k) for k in by_shape_a if k not in by_shape_b
        ],
        "unpaired_b": [
            shape_str(k) for k in by_shape_b if k not in by_shape_a
        ],
        "regressions": regressions,
        "improvements": improvements,
        "ok": not regressions,
    }


def _fmt(v: float) -> str:
    if abs(v) >= 1e6:
        return f"{v:.4g}"
    if v and abs(v) < 0.01:
        return f"{v:.2e}"
    return f"{v:g}"


def to_markdown(doc: dict) -> str:
    """The human half of the verdict."""
    lines = [
        f"# Bench diff: `{doc['a']}` → `{doc['b']}`",
        "",
        f"Paired shapes: {len(doc['pairs'])} · regressions: "
        f"{len(doc['regressions'])} · improvements: "
        f"{len(doc['improvements'])}",
        "",
    ]
    for pair in doc["pairs"]:
        lines.append(f"## {shape_str(tuple(pair['shape'].values()))}")
        lines.append("")
        lines.append("| field | a | b | Δ | Δ% | verdict |")
        lines.append("| --- | --- | --- | --- | --- | --- |")
        for name, e in pair["fields"].items():
            if e["verdict"] == "info":
                continue  # keep the table signal-dense
            relpct = "—" if e["rel"] is None else f"{100 * e['rel']:+.1f}%"
            mark = {"regression": "**REGRESSION**",
                    "improvement": "improvement",
                    "unchanged": ""}[e["verdict"]]
            lines.append(
                f"| `{name}` | {_fmt(e['a'])} | {_fmt(e['b'])} | "
                f"{_fmt(e['delta'])} | {relpct} | {mark} |"
            )
        lines.append("")
    for side, shapes in (("a", doc["unpaired_a"]),
                         ("b", doc["unpaired_b"])):
        if shapes:
            lines.append(
                f"Unpaired shapes in `{side}` — present in only one "
                "round, NOT compared (comparing across shapes is the "
                "r04→r05 640 ns confound):"
            )
            lines += [f"* {s}" for s in shapes]
            lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("a", help="older bench artifact")
    ap.add_argument("b", help="newer bench artifact")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the JSON verdict here")
    ap.add_argument("--md", dest="md_out", default=None,
                    help="write the markdown report here")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 when any paired field regressed")
    args = ap.parse_args(argv)

    doc = diff(args.a, args.b)
    md = to_markdown(doc)
    if args.md_out:
        with open(args.md_out, "w") as f:
            f.write(md + "\n")
    else:
        print(md)
    summary = {
        "paired": len(doc["pairs"]),
        "regressions": doc["regressions"],
        "unpaired_a": doc["unpaired_a"],
        "unpaired_b": doc["unpaired_b"],
        "ok": doc["ok"],
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps(summary))
    return 1 if args.fail_on_regression and not doc["ok"] else 0


if __name__ == "__main__":
    sys.exit(main())
