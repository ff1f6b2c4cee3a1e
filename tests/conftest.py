"""Test configuration: an 8-virtual-device CPU mesh unless the run is the
on-chip tier.

Sharding/collective tests run against CPU devices standing in for TPU chips —
the "fake backend" discipline the reference uses for its GPU CI
(.travis/test.sh runs the OpenCL suite on CPU drivers).
``LGBM_TPU_TESTS_ON_TPU=1`` leaves the platform to jax, which takes the
chip or fails at start-up (tests/test_tpu.py).
"""
import os
import sys

if os.environ.get("LGBM_TPU_TESTS_ON_TPU") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# persistent compile cache: repeat suite runs skip recompiles of unchanged
# jitted graphs (the grower's ~10 s XLA:CPU compiles dominate the fast tier)
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()

import subprocess

import numpy as np
import pytest

# Fast-tier discipline: the full suite takes ~18 min (native builds, the
# reference-CLI oracle, 8-device mesh trainings, multi-process sockets),
# which is too slow a loop for perf iteration.  Modules dominated by those
# costs are auto-marked `slow`; `pytest -m "not slow"` is the ~2-minute
# fast loop covering the pure-Python/JAX core.
SLOW_MODULES = {
    "test_parallel", "test_interop", "test_multiprocess", "test_streaming",
    "test_capi_train", "test_native", "test_convert_model", "test_tpu",
    "test_python_guide",
}
# individually measured >20s (full multi-model trainings); everything
# else in their modules stays in the fast tier
SLOW_TESTS = {
    "test_grid_search", "test_cv_and_cvbooster",
    "test_cv_lambdarank_group_folds",
    "test_bundled_training_matches_unbundled_exactly",
    # 8-device-mesh trainings (the packing x distributed composition);
    # the distributed learners themselves are covered by test_parallel in
    # the full tier
    "test_packed_distributed_matches_unpacked[voting]",
    "test_packed_distributed_matches_unpacked[data]",
    "test_feature_parallel_gates_packing_off",
}


# pinned in the FAST tier despite living in a slow module: the
# multi-process kill-and-resume byte-identity contract (ISSUE 6 acceptance)
# must gate every run, not just the full tier (~40 s, 3 worker pairs)
FAST_EXCEPTIONS = {
    "test_two_process_crash_resume_byte_identical",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name in FAST_EXCEPTIONS:
            continue
        # @pytest.mark.mesh8 is the opt-in the other way: a QUICK
        # 8-logical-device mesh training inside a slow module stays in
        # the fast tier, so tier-1 always carries a distributed-learner
        # job (the whole suite already runs on the forced 8-device CPU
        # mesh — see the XLA_FLAGS bootstrap above)
        if item.get_closest_marker("mesh8") is not None:
            continue
        if (item.module.__name__ in SLOW_MODULES
                or item.name in SLOW_TESTS):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def ref_bin():
    """Path to the reference LightGBM CLI — the interop oracle.

    Resolution order: $LGBM_REF_BIN → cached build in <repo>/.refbuild →
    cmake-build /root/reference on first use (reference tests/cpp_test
    discipline: the reference binary validates our model files)."""
    env = os.environ.get("LGBM_REF_BIN")
    if env and os.access(env, os.X_OK):
        return env
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    build_dir = os.path.join(root, ".refbuild")
    binpath = os.path.join(build_dir, "lightgbm")
    if os.access(binpath, os.X_OK):
        return binpath
    if not os.path.exists("/root/reference/CMakeLists.txt"):
        pytest.skip("reference source not available")
    os.makedirs(build_dir, exist_ok=True)
    try:
        subprocess.run(["cmake", "/root/reference", "-DCMAKE_BUILD_TYPE=Release"],
                       cwd=build_dir, check=True, capture_output=True,
                       timeout=300)
        subprocess.run(["make", "-j2", "lightgbm"], cwd=build_dir, check=True,
                       capture_output=True, timeout=1800)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            OSError) as e:
        pytest.skip(f"reference CLI build failed: {e}")
    finally:
        # the reference CMakeLists SETs EXECUTABLE_OUTPUT_PATH to its own
        # source dir (shadowing any -D override) — move the binary out so
        # /root/reference stays pristine
        stray = "/root/reference/lightgbm"
        if os.path.exists(stray):
            os.replace(stray, binpath)
    if not os.access(binpath, os.X_OK):
        pytest.skip("reference CLI build produced no binary")
    return binpath


@pytest.fixture(scope="session")
def reference_examples_available():
    """Whether the reference repo's bundled example datasets are mounted.

    The binary/regression fixtures silently fall back to synthetic data
    when they are not — tests asserting ORACLE numbers measured on the
    real datasets must check this and skip/re-scale instead of failing
    against data the oracle never saw."""
    return os.path.exists(
        "/root/reference/examples/binary_classification/binary.train")


@pytest.fixture(scope="session")
def binary_example():
    """Reference bundled binary classification example (7000 x 28)."""
    path = "/root/reference/examples/binary_classification/binary.train"
    test_path = "/root/reference/examples/binary_classification/binary.test"
    if os.path.exists(path):
        train = np.loadtxt(path)
        test = np.loadtxt(test_path)
    else:  # fallback synthetic data with similar shape
        rng = np.random.RandomState(0)
        w = rng.randn(28)
        X = rng.randn(7500, 28)
        y = (X @ w + 0.5 * rng.randn(7500) > 0).astype(np.float64)
        data = np.column_stack([y, X])
        train, test = data[:7000], data[7000:]
    return (train[:, 1:], train[:, 0], test[:, 1:], test[:, 0])


@pytest.fixture(scope="session")
def regression_example():
    path = "/root/reference/examples/regression/regression.train"
    test_path = "/root/reference/examples/regression/regression.test"
    if os.path.exists(path):
        train = np.loadtxt(path)
        test = np.loadtxt(test_path)
    else:
        rng = np.random.RandomState(1)
        w = rng.randn(28)
        X = rng.randn(7500, 28)
        y = X @ w + 0.3 * rng.randn(7500)
        data = np.column_stack([y, X])
        train, test = data[:7000], data[7000:]
    return (train[:, 1:], train[:, 0], test[:, 1:], test[:, 0])
