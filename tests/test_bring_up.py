"""Bring-up contracts (PR 21): nothing on the main path hides the device.

CPU-tier pins of what ``chip_smoke.py`` relies on: the smoke and the bench
refuse a host with no accelerator, the compile cache is placed by one
helper that defers to the environment, an unfusable layout resolves to a
histogram method that exists, a native library built from other sources
is rebuilt rather than loaded, and importing the package touches no
backend (one process owns the chip).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    return subprocess.run(
        [sys.executable] + args, cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _json_lines(text):
    out = []
    for line in text.splitlines():
        if line.strip().startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


def test_chip_smoke_refuses_cpu_naming_the_platform():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr, r.stderr[-500:]
    assert not _json_lines(r.stdout), r.stdout[-500:]


def test_bench_without_chip_exits_nonzero_with_the_childs_error():
    r = _run(["bench.py"])
    assert r.returncode != 0
    assert "wanted tpu, got cpu" in r.stderr, r.stderr[-500:]
    assert not _json_lines(r.stdout), r.stdout[-500:]


def test_package_import_initialises_no_backend():
    """Under a platform that does not exist any backend touch raises, so
    a clean import proves the parent of a chip-owning child stays off
    the device."""
    imports = ("import lightgbm_tpu, lightgbm_tpu.supervisor, "
               "lightgbm_tpu.serving, lightgbm_tpu.cli\n")
    r = _run(["-c", imports], JAX_PLATFORMS="no_such_platform")
    assert r.returncode == 0, r.stderr[-800:]
    r = _run(["-c", imports + "import jax; jax.devices()\n"],
             JAX_PLATFORMS="no_such_platform")
    assert r.returncode != 0 and "no_such_platform" in r.stderr


def test_cache_helper_defers_to_the_environment(monkeypatch):
    import jax
    from lightgbm_tpu.utils import cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cache.enable_persistent_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_persistent_cache() == os.path.join(
            ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_wide_layout_resolves_to_the_fused_kernel(monkeypatch):
    """600 histogram columns are two column tiles of the fused kernel, not
    a reason to leave it: a fused request stays fused (interpret mode
    here), no ``layout_downgrade`` fires, and the training runs on it."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.counters import counters
    counters.reset()
    rng = np.random.RandomState(0)
    X = rng.randn(300, 600)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
                     "min_data_in_leaf": 5, "enable_bin_packing": False,
                     "cpu_hist_method": "fused"},
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    verbose_eval=False)
    assert bst.inner.grower_cfg.hist_method == "fused"
    assert not counters.events("layout_downgrade")
    width = bst.inner.grower_cfg.max_bin
    assert set(counters.get("hist_dispatch")) == {
        f"col_tiles=2,fetch={fetch},hi=16,interpret=True,method=fused,"
        f"site={s},width={width}" for s, fetch in (("root", "block"),
                                                   ("split", "rows"))}
    assert bst.inner.models[0].num_leaves > 1


@pytest.mark.parametrize("chip,use_pallas,cpu_method,bins,weights,width,want,why", [
    # the chip's default layout: the fused kernel, nothing to say
    (True, True, "segment", "uint8", "float32", 255, "fused", None),
    # ROADMAP M2: 300 bins are uint16 bins and a taller hi one-hot
    (True, True, "segment", "uint16", "float32", 300, "fused", None),
    # past the kernel's widest hi one-hot
    (True, True, "segment", "uint16", "float32", 600, "einsum",
     "exceeds the fused kernel's limit 512"),
    (True, True, "segment", "int32", "float32", 255, "einsum",
     "bin dtype int32 is wider than 2 bytes"),
    (True, True, "segment", "uint8", "float64", 255, "einsum",
     "weights dtype float64 is not float32"),
    # use_pallas=false: the one way to force the XLA reference on the chip
    (True, False, "segment", "uint8", "float32", 255, "einsum", None),
    # off the chip cpu_hist_method is the method; tests put the
    # interpreted kernel there, behind the same gate
    (False, True, "segment", "uint8", "float32", 255, "segment", None),
    (False, True, "fused", "uint8", "float32", 255, "fused", None),
    (False, True, "fused", "uint16", "float32", 300, "fused", None),
    (False, True, "fused", "uint16", "float32", 600, "segment",
     "exceeds the fused kernel's limit 512"),
])
def test_hist_method_is_resolved_in_one_table(monkeypatch, chip, use_pallas,
                                              cpu_method, bins, weights,
                                              width, want, why):
    from lightgbm_tpu import grower
    monkeypatch.setattr(grower, "on_tpu", lambda: chip)
    method, reason = grower.resolve_hist_method(
        use_pallas, cpu_method, np.dtype(bins), np.dtype(weights), width)
    assert method == want
    assert (reason is None) if why is None else (why in reason), reason


def test_fused_config_on_a_refused_layout_raises_the_gates_reason():
    """``make_grower`` holds no second policy: a ``GrowerConfig`` that
    names the fused kernel on a layout the gate refuses is an error that
    says why, not a silent other method."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.grower import FeatureMeta, GrowerConfig, make_grower
    n, f, b = 256, 3, 600
    cfg = GrowerConfig(num_leaves=4, max_bin=b, hist_method="fused",
                       hist_interpret=True)
    meta = FeatureMeta(num_bin=jnp.full((f,), b, jnp.int32),
                       missing_type=jnp.zeros((f,), jnp.int32),
                       default_bin=jnp.zeros((f,), jnp.int32),
                       is_categorical=jnp.zeros((f,), bool))
    one = jnp.ones((n,), jnp.float32)
    with pytest.raises(ValueError, match="hist_method=fused cannot run on "
                       "this layout: histogram width 600 exceeds"):
        jax.jit(make_grower(cfg))(jnp.zeros((n, f), jnp.uint16), one, one,
                                  one, meta, jnp.ones((f,), bool))


def test_refused_layout_trains_on_the_reference_with_one_event():
    """600 bins through ``lgb.train`` with the fused kernel asked for: the
    one ``layout_downgrade`` comes from the booster's set-up, names the
    gate's reason, and ``grower_cfg.hist_method`` names what ran."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.counters import counters
    counters.reset()
    rng = np.random.RandomState(1)
    X = rng.randn(2000, 3)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
                     "max_bin": 600, "min_data_in_bin": 1,
                     "cpu_hist_method": "fused"},
                    lgb.Dataset(X, label=y), num_boost_round=2,
                    verbose_eval=False)
    assert bst.inner.grower_cfg.hist_method == "segment"
    evs = counters.events("layout_downgrade")
    assert [(e["stage"], e["requested"], e["resolved"]) for e in evs] == [
        ("boosting", "fused", "segment")]
    assert "fused kernel's limit 512" in evs[0]["reason"]
    assert set(counters.get("hist_dispatch")) == {
        f"interpret=False,method=segment,site={site}"
        for site in ("root", "split")}


def test_native_library_with_another_source_hash_is_rebuilt(tmp_path,
                                                            monkeypatch):
    from lightgbm_tpu import native
    lib_path = tmp_path / "_gbt_native.so"
    key_path = tmp_path / "_gbt_native.so.key"
    builds = []

    def fake_compile(out_path):
        builds.append(out_path)
        with open(out_path, "w") as f:
            f.write("built from the current sources")
        return True

    monkeypatch.setattr(native, "_LIB_PATH", str(lib_path))
    monkeypatch.setattr(native, "_KEY_PATH", str(key_path))
    monkeypatch.setattr(native, "_compile", fake_compile)
    monkeypatch.setattr(native, "_bind", lambda lib: lib)
    monkeypatch.setattr(native.ctypes, "CDLL",
                        lambda path: open(path).read())
    monkeypatch.delenv("LGBM_TPU_NO_NATIVE", raising=False)

    def load():
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", False)
        return native.get_lib()

    # a binary copied in from a checkout with other sources
    lib_path.write_text("stale binary")
    key_path.write_text("0" * 64)
    assert load() == "built from the current sources"
    assert len(builds) == 1
    assert key_path.read_text() == native._source_key()
    # same sources again: loaded as is
    assert load() == "built from the current sources"
    assert len(builds) == 1
    # a binary with no recorded key at all (the pre-PR-21 layout)
    key_path.unlink()
    load()
    assert len(builds) == 2
