"""Test config: force CPU with 8 virtual devices BEFORE jax is imported.

Distributed logic is tested on a virtual CPU mesh, as the reference tests
its distributed trainer on the in-process MULTI_THREAD backend
(ydf/learner/.../distributed_gradient_boosted_trees_test.cc:62-70).
"""

import os

# Tests run on the virtual CPU mesh whatever the environment says. Some
# pytest plugins (jaxtyping) import jax before this conftest, baking the
# env value into jax.config — so override the config too, not just the
# env var.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
# Entry points the suite calls in-process (__graft_entry__) turn the
# persistent compile cache on; the suite compiles each program once, so
# keep JAX's own switch off and the checkout clean.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

REFERENCE_DATASET_DIR = "/root/reference/yggdrasil_decision_forests/test_data/dataset"


@pytest.fixture(scope="session")
def adult_train():
    import pandas as pd

    return pd.read_csv(os.path.join(REFERENCE_DATASET_DIR, "adult_train.csv"))


@pytest.fixture(scope="session")
def adult_test():
    import pandas as pd

    return pd.read_csv(os.path.join(REFERENCE_DATASET_DIR, "adult_test.csv"))


@pytest.fixture(scope="session")
def abalone():
    import pandas as pd

    return pd.read_csv(os.path.join(REFERENCE_DATASET_DIR, "abalone.csv"))


@pytest.fixture(scope="session")
def iris_df():
    import pandas as pd

    return pd.read_csv(os.path.join(REFERENCE_DATASET_DIR, "iris.csv"))


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_cache_growth():
    """Clears JAX's tracing/compilation caches at every module boundary.

    A full single-process run of this suite accumulates hundreds of
    XLA-CPU compilations; at roughly the 35-40 minute mark the process
    segfaulted INSIDE XLA's backend_compile_and_load (captured with
    faulthandler, docs/xla_cpu_segfault.md) in rounds 4 and 5 — an
    XLA-CPU-side failure under compile-cache/memory accumulation, which
    the sharded harness masked by process recycling. Clearing per module
    bounds the growth the same way without giving up the single-process
    run; per-module tests still share compilations (the expensive
    within-file reuse), and fresh processes are unaffected."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()
