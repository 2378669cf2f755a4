"""Command-line tools.

Counterparts of the reference CLI binaries (`ydf/cli/`: train.cc,
predict.cc, evaluate.cc, infer_dataspec.cc, show_dataspec.cc,
show_model.cc, benchmark_inference.cc, utils/synthetic_dataset.cc) as one
argparse entry point:

    python -m ydf_tpu.cli train --dataset csv:train.csv --label y \
        --learner GRADIENT_BOOSTED_TREES --output /tmp/model
    python -m ydf_tpu.cli predict --model /tmp/model --dataset csv:test.csv
    python -m ydf_tpu.cli evaluate --model /tmp/model --dataset csv:test.csv
    python -m ydf_tpu.cli show_model --model /tmp/model
    python -m ydf_tpu.cli infer_dataspec --dataset csv:train.csv
    python -m ydf_tpu.cli benchmark_inference --model m --dataset csv:d.csv
    python -m ydf_tpu.cli synthetic_dataset --output csv:/tmp/syn.csv
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _force_cpu_if_requested(args):
    if getattr(args, "cpu", False):
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")


_LEARNERS = {
    "GRADIENT_BOOSTED_TREES": "GradientBoostedTreesLearner",
    "RANDOM_FOREST": "RandomForestLearner",
    "CART": "CartLearner",
    "ISOLATION_FOREST": "IsolationForestLearner",
}


def cmd_train(args):
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf
    from ydf_tpu.config import Task
    from ydf_tpu.utils import log, telemetry

    if getattr(args, "telemetry_dir", None):
        # Post-import arming (the env var is parsed before argv exists);
        # train() flushes the trace + metrics dump there.
        telemetry.configure(directory=args.telemetry_dir)
    from ydf_tpu.utils import telemetry_http

    if getattr(args, "metrics_port", None) is not None:
        srv = telemetry_http.start_metrics_server(args.metrics_port)
        log.info(f"metrics endpoints on 127.0.0.1:{srv.port}")
    else:
        telemetry_http.maybe_start_from_env()
    cls = getattr(ydf, _LEARNERS[args.learner])
    kwargs = json.loads(args.hyperparameters) if args.hyperparameters else {}
    if args.learner == "ISOLATION_FOREST":
        learner = cls(**kwargs)
    else:
        if not args.label:
            sys.exit(
                f"error: --label is required for learner {args.learner}"
            )
        learner = cls(label=args.label, task=Task(args.task), **kwargs)
    if getattr(args, "working_dir", None):
        learner.working_dir = args.working_dir
    if getattr(args, "resume", False):
        learner.resume_training = True
    data = args.dataset
    if getattr(args, "workers", None):
        # Feature-parallel distributed training: --dataset names a
        # feature-sharded dataset cache directory and --workers the
        # running `ydf_tpu.cli worker` fleet
        # (docs/distributed_training.md).
        from ydf_tpu.dataset.cache import DatasetCache

        learner.distributed_workers = [
            a.strip() for a in args.workers.split(",") if a.strip()
        ]
        if not learner.distributed_workers:
            sys.exit("error: --workers lists no addresses")
        data = DatasetCache(args.dataset)
    t0 = time.time()
    try:
        model = learner.train(data)
    except Exception as e:
        # Preemption (SIGTERM/SIGINT during checkpointed training) is a
        # RESUMABLE outcome, not a failure: exit with its distinct code
        # (75, EX_TEMPFAIL) so schedulers requeue with --resume instead
        # of treating the job as crashed.
        from ydf_tpu.learners.gbt import TrainingPreempted

        if isinstance(e, TrainingPreempted):
            log.info(f"preempted: {e}")
            sys.exit(TrainingPreempted.exit_code)
        raise
    log.info(f"Trained in {time.time() - t0:.2f}s")
    model.save(args.output)
    if getattr(args, "telemetry_dir", None):
        telemetry.flush()
        log.info(f"telemetry written to {args.telemetry_dir}")
    print(f"Model saved to {args.output}")


def cmd_predict(args):
    _force_cpu_if_requested(args)
    import numpy as np

    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    preds = model.predict(args.dataset)
    out = args.output
    preds = np.asarray(preds)
    if out:
        np.savetxt(out, preds.reshape(len(preds), -1), delimiter=",")
        print(f"Predictions written to {out}")
    else:
        for row in preds.reshape(len(preds), -1):
            print(",".join(f"{v:.6g}" for v in row))


def cmd_evaluate(args):
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    ev = model.evaluate(
        args.dataset, confidence_intervals=args.confidence_intervals
    )
    print(ev)


def cmd_infer_dataspec(args):
    import ydf_tpu as ydf

    ds = ydf.Dataset.from_data(args.dataset)
    print(ds.dataspec)


def cmd_show_dataspec(args):
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    print(model.dataspec)


def cmd_show_model(args):
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    print(model.describe())


def cmd_benchmark_inference(args):
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    r = model.benchmark(args.dataset, num_runs=args.num_runs)
    r["ns_per_example"] = round(r["ns_per_example"], 1)
    print(json.dumps(r))


def cmd_analyze(args):
    """Reference cli/analyze_model_and_dataset.cc: PDP + permutation
    importances, text to stdout or an HTML report file."""
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    analysis = model.analyze(args.dataset)
    if args.output:
        with open(args.output, "w") as f:
            f.write(analysis.to_html())
        print(f"Analysis written to {args.output}")
    else:
        print(analysis)


def cmd_compute_variable_importances(args):
    """Reference cli/compute_variable_importances.cc: permutation
    importances on a dataset, printed per metric."""
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf
    from ydf_tpu.analysis.importance import permutation_importance

    model = ydf.load_model(args.model)
    vi = permutation_importance(
        model, args.dataset, num_rounds=args.num_repetitions
    )
    if vi:
        print(f"MEAN_DECREASE_IN_{vi[0]['metric'].upper()}:")
    for e in vi:
        print(f"  {e['importance']:+.6f}  {e['feature']}")


def cmd_edit_model(args):
    """Reference cli/edit_model.cc: structural edits on a saved model —
    keep the first N trees and/or strip training metadata."""
    _force_cpu_if_requested(args)
    import ydf_tpu as ydf

    model = ydf.load_model(args.model)
    if args.keep_trees is not None:
        if not 1 <= args.keep_trees <= model.num_trees():
            sys.exit(
                f"error: --keep_trees must be in [1, {model.num_trees()}]"
            )
        K = int(getattr(model, "num_trees_per_iter", 1) or 1)
        if args.keep_trees % K != 0:
            # Multiclass GBT stores K trees per iteration; a partial
            # iteration would skew one class's logit.
            sys.exit(
                f"error: --keep_trees must be a multiple of "
                f"num_trees_per_iter={K}"
            )
        model.forest = model.forest.truncated(args.keep_trees)
        if hasattr(model, "_dim_forests"):
            del model._dim_forests
    if args.pure_serving:
        # MakePureServing (abstract_model.h:433): drop training artifacts.
        model.extra_metadata.pop("tuner_logs", None)
        if hasattr(model, "training_logs"):
            model.training_logs = {}
        if hasattr(model, "oob_evaluation"):
            model.oob_evaluation = None
        if hasattr(model, "oob_variable_importances"):
            model.oob_variable_importances = None
    model.save(args.output)
    print(f"Edited model saved to {args.output}")


def cmd_convert_dataset(args):
    """Reference cli/convert_dataset.cc: re-encode a dataset. Outputs:
    csv:<path> (normalized CSV) or cache:<dir> (the out-of-core binned
    cache, dataset/cache.py — requires --label)."""
    _force_cpu_if_requested(args)
    if args.output.startswith("cache:"):
        from ydf_tpu.config import Task
        from ydf_tpu.dataset.cache import create_dataset_cache

        if not args.label:
            sys.exit("error: cache: output requires --label")
        cache = create_dataset_cache(
            args.input, args.output[len("cache:"):], label=args.label,
            task=Task(args.task),
        )
        print(
            f"Cache with {cache.num_rows} rows written to {cache.path}"
        )
        return
    from ydf_tpu.dataset.dataset import Dataset

    ds = Dataset.from_data(args.input)
    out = args.output
    if out.startswith(("tfrecord:", "tfrecord-nocompression:")):
        from ydf_tpu.dataset.tfrecord import write_tfrecord_columns

        compressed = out.startswith("tfrecord:")
        path = out.partition(":")[2]
        write_tfrecord_columns(path, ds.data, compressed=compressed)
        print(f"Wrote {ds.num_rows} rows to {path}")
        return
    import pandas as pd

    if out.startswith("csv:"):
        out = out[4:]
    pd.DataFrame(ds.data).to_csv(out, index=False)
    print(f"Wrote {ds.num_rows} rows to {out}")


def cmd_synthetic_dataset(args):
    """Config-driven generator (reference dataset/synthetic_dataset.cc)."""
    import numpy as np

    rng = np.random.RandomState(args.seed)
    n, fnum, fcat = args.num_examples, args.num_numerical, args.num_categorical
    cols = {}
    logit = np.zeros(n)
    for i in range(fnum):
        x = rng.normal(size=n)
        cols[f"num_{i}"] = x
        if i % 2 == 0:
            logit += x * (1.0 / (i + 1))
        else:
            logit += np.sin(2 * x) * 0.5
    for i in range(fcat):
        vocab = [f"v{j}" for j in range(args.categorical_vocab_size)]
        c = rng.randint(0, len(vocab), size=n)
        cols[f"cat_{i}"] = np.array(vocab)[c]
        logit += (c == 0) * 0.5
    if args.task == "CLASSIFICATION":
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logit))).astype(np.int64)
        cols["label"] = np.where(y == 1, "pos", "neg")
    else:
        cols["label"] = logit + rng.normal(scale=0.2, size=n)

    import pandas as pd

    path = args.output
    if path.startswith("csv:"):
        path = path[4:]
    pd.DataFrame(cols).to_csv(path, index=False)
    print(f"Wrote {n} examples to {path}")


def cmd_hyperparameters(args):
    """Machine-readable spec of one learner (JSON) or the generated doc
    page for all learners (reference learner/export_doc.cc +
    wrapper_generator.cc)."""
    from ydf_tpu.hyperparameters import (
        default_learner_classes,
        format_documentation,
        hyperparameter_spec,
    )

    if args.learner:
        import ydf_tpu as ydf

        cls = getattr(ydf, _LEARNERS[args.learner])
        spec = hyperparameter_spec(cls)
        print(json.dumps(
            {name: hp.to_json() for name, hp in spec.items()}, indent=2
        ))
    else:
        print(format_documentation(default_learner_classes()))


def cmd_distribute(args):
    """Fan a list of shell commands out over a worker pool — the
    reference's distribute_cli (utils/distribute_cli/distribute_cli.h:
    15-31: "distribute the execution of command lines"). Workers here are
    local processes (one per --workers slot); on a multi-host TPU pod the
    same file is run once per host with --shard i/--num_shards N so each
    host takes every N-th command. Failed commands are reported at the
    end and set a non-zero exit code; --keep_going controls whether the
    pool drains after a failure (the reference's behavior)."""
    import subprocess
    import sys
    from concurrent.futures import ThreadPoolExecutor

    with open(args.commands) as f:
        commands = [
            ln.strip() for ln in f
            if ln.strip() and not ln.strip().startswith("#")
        ]
    commands = commands[args.shard:: args.num_shards]
    if not commands:
        print("no commands to run")
        return
    failures = []
    stop = {"flag": False}

    def run_one(item):
        i, cmd = item
        if stop["flag"]:
            return
        r = subprocess.run(cmd, shell=True)
        if r.returncode != 0:
            failures.append((i, cmd, r.returncode))
            if not args.keep_going:
                stop["flag"] = True

    with ThreadPoolExecutor(max_workers=max(args.workers, 1)) as pool:
        list(pool.map(run_one, enumerate(commands)))
    done = len(commands) - len(failures)
    print(f"distribute: {done}/{len(commands)} commands succeeded")
    for i, cmd, rc in failures:
        print(f"  FAILED [{i}] rc={rc}: {cmd}")
    if failures:
        sys.exit(1)


def cmd_worker(args):
    """Remote train/evaluate worker (reference ydf.start_worker /
    generic_worker.h): serves HyperParameterOptimizerLearner(workers=...)
    trial requests until shut down. The transport executes requests from
    the manager (like the reference's distribute workers), so bind
    beyond loopback (--host 0.0.0.0) only on trusted job networks."""
    _force_cpu_if_requested(args)
    from ydf_tpu.parallel.worker_service import start_worker

    print(f"worker listening on {args.host}:{args.port}", flush=True)
    start_worker(
        args.port, host=args.host,
        metrics_port=getattr(args, "metrics_port", None),
    )


def main(argv=None):
    from ydf_tpu.config import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(prog="ydf_tpu", description=__doc__)
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser(
        "worker",
        help="serve remote train/evaluate requests for distributed "
             "hyperparameter tuning (reference ydf.start_worker)",
    )
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address; 0.0.0.0 only on trusted networks")
    p.add_argument("--metrics_port", type=int,
                   help="serve /metrics /healthz /statusz on this "
                        "loopback port (0 = ephemeral; same as "
                        "YDF_TPU_METRICS_PORT — docs/observability.md)")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_worker)

    p = sub.add_parser(
        "distribute",
        help="run a file of shell commands over a local worker pool "
             "(reference utils/distribute_cli)",
    )
    p.add_argument("--commands", required=True,
                   help="file with one shell command per line; # comments")
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--shard", type=int, default=0,
                   help="this host's index (multi-host: run once per host)")
    p.add_argument("--num_shards", type=int, default=1)
    p.add_argument("--keep_going", action="store_true",
                   help="keep scheduling after a failure")
    p.set_defaults(fn=cmd_distribute)

    p = sub.add_parser(
        "hyperparameters",
        help="print a learner's hyperparameter spec (JSON) or, with no "
             "--learner, the full generated markdown doc page",
    )
    p.add_argument("--learner", choices=sorted(_LEARNERS))
    p.set_defaults(fn=cmd_hyperparameters)

    p = sub.add_parser("train")
    p.add_argument("--dataset", required=True)
    p.add_argument("--label")
    p.add_argument("--task", default="CLASSIFICATION")
    p.add_argument("--learner", default="GRADIENT_BOOSTED_TREES",
                   choices=sorted(_LEARNERS))
    p.add_argument("--output", required=True)
    p.add_argument("--hyperparameters", help="JSON dict of learner kwargs")
    p.add_argument("--working_dir",
                   help="snapshot directory for checkpointed training "
                        "(enables preemption-safe SIGTERM handling; "
                        "exit code 75 = resumable). Works with "
                        "--workers too: the distributed manager "
                        "snapshots at tree boundaries and a new "
                        "manager can --resume after the old one died "
                        "(docs/distributed_training.md \"Resume\")")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest snapshot in "
                        "--working_dir (single-machine or "
                        "distributed; a snapshot whose worker/shard "
                        "config fingerprint mismatches the flags is "
                        "refused with a clear error)")
    p.add_argument("--telemetry_dir",
                   help="write chrome-tracing spans + a Prometheus "
                        "metrics dump here (same as "
                        "YDF_TPU_TELEMETRY_DIR; see "
                        "docs/observability.md)")
    p.add_argument("--metrics_port", type=int,
                   help="serve /metrics /healthz /statusz on this "
                        "loopback port while training (0 = ephemeral; "
                        "same as YDF_TPU_METRICS_PORT)")
    p.add_argument("--workers",
                   help="comma-separated host:port addresses of "
                        "`ydf_tpu.cli worker` processes for "
                        "distributed training; --dataset must then "
                        "name a dataset cache directory created with "
                        "feature_shards=N (feature-parallel) or "
                        "row_shards=N (row-parallel; both = hybrid) "
                        "(docs/distributed_training.md)")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("predict")
    p.add_argument("--model", required=True)
    p.add_argument("--dataset", required=True)
    p.add_argument("--output")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_predict)

    p = sub.add_parser("evaluate")
    p.add_argument("--model", required=True)
    p.add_argument("--dataset", required=True)
    p.add_argument("--confidence_intervals", action="store_true")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_evaluate)

    p = sub.add_parser("infer_dataspec")
    p.add_argument("--dataset", required=True)
    p.set_defaults(fn=cmd_infer_dataspec)

    p = sub.add_parser("show_dataspec")
    p.add_argument("--model", required=True)
    p.set_defaults(fn=cmd_show_dataspec)

    p = sub.add_parser("show_model")
    p.add_argument("--model", required=True)
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_show_model)

    p = sub.add_parser("benchmark_inference")
    p.add_argument("--model", required=True)
    p.add_argument("--dataset", required=True)
    p.add_argument("--num_runs", type=int, default=10)
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_benchmark_inference)

    p = sub.add_parser("analyze")
    p.add_argument("--model", required=True)
    p.add_argument("--dataset", required=True)
    p.add_argument("--output", help="write an HTML report here")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("compute_variable_importances")
    p.add_argument("--model", required=True)
    p.add_argument("--dataset", required=True)
    p.add_argument("--num_repetitions", type=int, default=1)
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_compute_variable_importances)

    p = sub.add_parser("edit_model")
    p.add_argument("--model", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--keep_trees", type=int)
    p.add_argument("--pure_serving", action="store_true")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_edit_model)

    p = sub.add_parser("convert_dataset")
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--label")
    p.add_argument("--task", default="CLASSIFICATION")
    p.add_argument("--cpu", action="store_true")
    p.set_defaults(fn=cmd_convert_dataset)

    p = sub.add_parser("synthetic_dataset")
    p.add_argument("--output", required=True)
    p.add_argument("--num_examples", type=int, default=10000)
    p.add_argument("--num_numerical", type=int, default=8)
    p.add_argument("--num_categorical", type=int, default=2)
    p.add_argument("--categorical_vocab_size", type=int, default=10)
    p.add_argument("--task", default="CLASSIFICATION",
                   choices=["CLASSIFICATION", "REGRESSION"])
    p.add_argument("--seed", type=int, default=1234)
    p.set_defaults(fn=cmd_synthetic_dataset)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
