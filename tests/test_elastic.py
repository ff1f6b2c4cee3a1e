"""Elastic training groups (ISSUE 14): topology-change resume, the
degraded-world self-healing loop, and the incarnation epoch fence.

The byte-identity tests lean on an integer-valued-gradient objective:
every histogram sum is exact in f32 regardless of summation order, so
"the model after a topology change is byte-identical to the
uninterrupted run" is a meaningful pin, not a tolerance check.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import checkpoint as ck
from lightgbm_tpu.obs.counters import counters
from lightgbm_tpu.parallel import mesh, sync
from lightgbm_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = dict(objective="regression", num_leaves=15, min_data_in_leaf=10,
            learning_rate=0.5, verbose=-1, boost_from_average=False)


@pytest.fixture(autouse=True)
def _clean_slate():
    faults.clear()
    counters.reset()
    yield
    faults.clear()


def _problem(n=1600):
    rng = np.random.RandomState(7)
    X = (rng.randint(0, 24, size=(n, 8)) / 4.0).astype(np.float32)
    w = rng.randn(8)
    y = np.rint((X @ w) - np.median(X @ w)).astype(np.float32)
    return X, y


def _int_fobj(preds, ds):
    y = np.asarray(ds.get_label(), np.float32)
    g = np.clip(np.rint(np.asarray(preds, np.float64) - y), -64, 64)
    return g.astype(np.float32), np.ones_like(g, np.float32)


# two-rank worker: trains its half of the SAME problem; knobs travel as
# env vars so one script serves both the "commit a 2-rank set" leg and
# the "grow 1 -> 2 through elastic resume" leg
WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
import numpy as np
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()
import lightgbm_tpu as lgb

rng = np.random.RandomState(7)
n = 1600
X = (rng.randint(0, 24, size=(n, 8)) / 4.0).astype(np.float32)
w = rng.randn(8)
y = np.rint((X @ w) - np.median(X @ w)).astype(np.float32)

def int_fobj(preds, ds):
    lab = np.asarray(ds.get_label(), np.float32)
    g = np.clip(np.rint(np.asarray(preds, np.float64) - lab), -64, 64)
    return g.astype(np.float32), np.ones_like(g, np.float32)

rank = int(os.environ["LGBM_TPU_RANK"])
lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)
if os.environ.get("EL_SWAP") == "1":
    # re-partitioned job: each rank claims its old global row window but
    # actually holds the OTHER half — the global fingerprint audit must
    # catch the lie on resume
    lo, hi = (n // 2, n) if rank == 0 else (0, n // 2)
params = dict(objective="regression", num_leaves=15, min_data_in_leaf=10,
              learning_rate=0.5, verbose=-1, boost_from_average=False,
              tree_learner="data", num_machines=2,
              machine_list_file=os.environ["EL_MLIST"],
              output_model=os.environ["EL_OUT"])
if os.environ.get("EL_IMPL"):
    params["parallel_impl"] = os.environ["EL_IMPL"]
if os.environ.get("EL_SNAPFREQ"):
    params["snapshot_freq"] = int(os.environ["EL_SNAPFREQ"])
if os.environ.get("EL_RESUME") == "1":
    params["snapshot_resume"] = True
    params["elastic_resume"] = True
expect = os.environ.get("EL_EXPECT", "")
try:
    bst = lgb.train(params, lgb.Dataset(X[lo:hi], label=y[lo:hi]),
                    num_boost_round=int(os.environ["EL_ROUNDS"]),
                    verbose_eval=False, fobj=int_fobj)
except Exception as e:
    from lightgbm_tpu.checkpoint import CheckpointError
    assert expect, e
    assert isinstance(e, CheckpointError), (type(e).__name__, e)
    assert expect in str(e), e
    print("EXPECTED_REJECT", rank)
    print("ELASTIC_WORKER_OK", rank)
    sys.exit(0)
assert not expect, f"expected a {expect} rejection, but training ran"
bst.save_model(os.environ["EL_OUT"] + f".final_{rank}")
print("ELASTIC_WORKER_OK", rank)
"""


def _run_pair(workdir, out, *, rounds, snapfreq=None, resume=False,
              impl=None, swap=False, expect=None):
    script = os.path.join(workdir, "elastic_worker.py")
    with open(script, "w") as f:
        f.write(WORKER)
    mlist = os.path.join(workdir, "mlist.txt")
    with open(mlist, "w") as f:
        f.write("127.0.0.1 0\n127.0.0.1 0\n")
    mesh.refresh_local_ports(mlist)
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update(PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
                   LGBM_TPU_RANK=str(rank), EL_MLIST=mlist, EL_OUT=out,
                   EL_ROUNDS=str(rounds), JAX_PLATFORMS="cpu",
                   EL_SNAPFREQ=str(snapfreq) if snapfreq else "",
                   EL_RESUME="1" if resume else "",
                   EL_IMPL=impl or "", EL_SWAP="1" if swap else "",
                   EL_EXPECT=expect or "")
        procs.append(subprocess.Popen([sys.executable, script],
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True,
                                      env=env))
    outs = []
    for i, p in enumerate(procs):
        o, _ = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {i}:\n{o[-5000:]}"
        outs.append(o)
    return outs


@pytest.fixture(scope="module")
def serial5():
    """Uninterrupted 5-round single-process baseline."""
    X, y = _problem()
    bst = lgb.train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=5,
                    verbose_eval=False, fobj=_int_fobj)
    return bst.model_to_string(-1)


@pytest.fixture(scope="module")
def two_rank_set(tmp_path_factory):
    """A committed 2-rank elastic snapshot set at iteration 3."""
    d = tmp_path_factory.mktemp("elastic_w2")
    out = str(d / "model.txt")
    _run_pair(str(d), out, rounds=3, snapfreq=3)
    assert os.path.exists(ck.manifest_path(out, 3))
    return out


# ------------------------------------------------- topology-change resume

def test_shrink_resume_2_to_1_byte_identical(two_rank_set, serial5):
    """Acceptance: a committed W=2 set loads at W'=1 — one process on the
    union of both shards continues to the byte-identical uninterrupted
    model, adds ZERO collectives, and says so in a structured event."""
    X, y = _problem()
    params = dict(BASE, output_model=two_rank_set, snapshot_resume=True,
                  elastic_resume=True)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5,
                    verbose_eval=False, fobj=_int_fobj)
    assert bst.model_to_string(-1) == serial5
    evs = counters.events("elastic_resume")
    assert evs, "no elastic_resume event behind the topology change"
    assert evs[-1]["old_world"] == 2 and evs[-1]["new_world"] == 1
    assert evs[-1]["iteration"] == 3
    assert evs[-1]["rows"] == [0, 1600]
    assert counters.get("collective_calls") == {}


def test_grow_resume_1_to_2_byte_identical(tmp_path, serial5):
    """The other direction: a single-process snapshot set loads at W'=2 —
    both ranks agree and match the uninterrupted serial run."""
    X, y = _problem()
    out = str(tmp_path / "model.txt")
    lgb.train(dict(BASE, output_model=out, snapshot_freq=3),
              lgb.Dataset(X, label=y), num_boost_round=3,
              verbose_eval=False, fobj=_int_fobj)
    _run_pair(str(tmp_path), out, rounds=5, resume=True)
    with open(out + ".final_0") as f:
        m0 = f.read()
    with open(out + ".final_1") as f:
        m1 = f.read()
    assert m0 == m1, "the two grown ranks disagree"
    assert m0 == serial5


def test_strict_resume_refuses_topology_change(two_rank_set):
    """Pinned default: without elastic_resume the STRICT group resume
    treats a topology change as a structured fatal, and the message names
    the knob that would allow it."""
    def gather1(payload):
        ok, fatal = ck._local_valid_group_iters(two_rank_set, 0, 1, None)
        return [{"rank": 0, "ok": ok, "fatal": fatal}]

    with pytest.raises(ck.CheckpointError, match="elastic_resume"):
        ck.find_latest_valid_group(two_rank_set, rank=0, world=1,
                                   fingerprint=None, gather=gather1)


# ------------------------------------------------- incarnation epoch fence

def test_stale_epoch_frame_rejected(monkeypatch):
    """A frame from a dead incarnation is rejected terminally: the error
    names BOTH epochs, no retry is burned (a stale process cannot become
    current by retrying), and a structured event records the rejection.
    Runs entirely in-process — zero sockets, zero hang risk."""
    monkeypatch.setenv(ck.GROUP_EPOCH_ENV, "3")
    faults.install("stale_rejoin")
    with pytest.raises(sync.StaleEpochError) as ei:
        sync.allgather_object({"probe": 1})
    e = ei.value
    assert e.frame_epoch == 2 and e.group_epoch == 3
    assert "epoch 2" in str(e) and "epoch 3" in str(e)
    assert counters.get("collective_retries") == {}
    evs = counters.events("stale_epoch_rejected")
    assert evs and evs[-1]["op"] == "allgather_object"
    assert evs[-1]["frame_epoch"] == 2 and evs[-1]["group_epoch"] == 3


def test_epoch_fence_unit():
    """The fence itself: current-epoch frames pass, any other epoch
    raises with both epochs attached."""
    assert sync._check_frame_epoch(0, "broadcast_object") is None
    with pytest.raises(sync.StaleEpochError) as ei:
        sync._check_frame_epoch(5, "broadcast_object", peer=1)
    assert ei.value.frame_epoch == 5 and ei.value.group_epoch == 0


def test_stale_incarnation_refused_at_startup_barrier(tmp_path,
                                                      monkeypatch):
    """ISSUE 18: the epoch fence extends to the ``jax.distributed``
    STARTUP barrier — a worker launched under an older incarnation epoch
    (the supervisor stamps the group's current epoch on disk per
    relaunch) is refused BEFORE it can touch the new group's rendezvous,
    with the same terminal StaleEpochError + structured event as the
    per-payload fence."""
    import types
    out = str(tmp_path / "m.txt")
    ck.write_group_epoch_file(out, 7)
    assert ck.read_group_epoch_file(out) == 7
    monkeypatch.setenv(ck.GROUP_EPOCH_ENV, "5")
    cfg = types.SimpleNamespace(num_machines=2, output_model=out,
                                machine_list_file="")
    with pytest.raises(sync.StaleEpochError) as ei:
        mesh.init_distributed_from_config(cfg)
    assert ei.value.frame_epoch == 5 and ei.value.group_epoch == 7
    assert "epoch 5" in str(ei.value) and "epoch 7" in str(ei.value)
    evs = counters.events("stale_epoch_rejected")
    assert evs and evs[-1]["op"] == "distributed_init"
    assert evs[-1]["frame_epoch"] == 5 and evs[-1]["group_epoch"] == 7


def test_elastic_armed_single_process_zero_collectives(tmp_path):
    """comm_audit contract: arming elastic_resume (snapshots + resume +
    the elastic finder) adds ZERO host-object collectives to
    single-process training."""
    rng = np.random.RandomState(0)
    X = rng.randn(300, 6)
    y = (X @ rng.randn(6) > 0).astype(np.float64)
    out = str(tmp_path / "m.txt")
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  telemetry=True, snapshot_freq=2, output_model=out,
                  elastic_resume=True, preempt_signal="sigterm")
    ds = lambda: lgb.Dataset(X, label=y, free_raw_data=False)  # noqa: E731
    lgb.train(params, ds(), num_boost_round=4, verbose_eval=False,
              resume=True)
    counters.reset()
    # the second run exercises the elastic finder against a real snapshot
    lgb.train(params, ds(), num_boost_round=4, verbose_eval=False,
              resume=True)
    assert counters.events("elastic_resume")
    assert counters.get("collective_calls") == {}
    assert counters.get("collective_bytes") == {}


# ------------------------------- elastic GSPMD (ISSUE 18): topology errors

@pytest.fixture(scope="module")
def gspmd_two_rank_set(tmp_path_factory):
    """A committed 2-rank elastic snapshot set at iteration 3, trained by
    the compiler-owned GSPMD grower (multi-process ``parallel_impl=gspmd``
    over the named (batch, feature) mesh)."""
    d = tmp_path_factory.mktemp("elastic_gspmd_w2")
    out = str(d / "model.txt")
    _run_pair(str(d), out, rounds=3, snapfreq=3, impl="gspmd")
    assert os.path.exists(ck.manifest_path(out, 3))
    return out


def _copy_set(src_out, dst_dir):
    """Copy a snapshot-set prefix into ``dst_dir`` so a test can mutilate
    its own copy without poisoning the module-scoped fixture."""
    import shutil
    src_dir = os.path.dirname(src_out)
    for fn in os.listdir(src_dir):
        p = os.path.join(src_dir, fn)
        if os.path.isfile(p):
            shutil.copy(p, os.path.join(str(dst_dir), fn))
    return os.path.join(str(dst_dir), os.path.basename(src_out))


def test_gspmd_strict_resume_refuses_topology_change(gspmd_two_rank_set):
    """PR 12 pin mirrored onto a GSPMD-committed set: without
    elastic_resume, the strict group resume treats a topology change as a
    structured fatal naming the knob that would allow it."""
    def gather1(payload):
        ok, fatal = ck._local_valid_group_iters(gspmd_two_rank_set, 0, 1,
                                                None)
        return [{"rank": 0, "ok": ok, "fatal": fatal}]

    with pytest.raises(ck.CheckpointError, match="elastic_resume"):
        ck.find_latest_valid_group(gspmd_two_rank_set, rank=0, world=1,
                                   fingerprint=None, gather=gather1)


def test_gspmd_repartitioned_data_fails_fingerprint_audit(
        gspmd_two_rank_set, tmp_path):
    """Resuming a GSPMD group on RE-PARTITIONED data (each rank claims
    its old global row window but holds the other half) must fail the
    global fingerprint audit on ALL ranks — a structured CheckpointError
    naming the fingerprint, not silent training on misattributed rows."""
    out = _copy_set(gspmd_two_rank_set, tmp_path)
    outs = _run_pair(str(tmp_path), out, rounds=5, resume=True,
                     impl="gspmd", swap=True, expect="fingerprint")
    for rank, o in enumerate(outs):
        assert f"EXPECTED_REJECT {rank}" in o, o[-3000:]


def test_gspmd_torn_shard_demotes_group(gspmd_two_rank_set, serial5,
                                        tmp_path):
    """A torn shard on ANY rank of the GSPMD-committed set demotes the
    whole set for elastic resume (checkpoint_skipped, never half-loaded):
    with no older set, the single-process job trains from scratch to the
    byte-identical uninterrupted model."""
    out = _copy_set(gspmd_two_rank_set, tmp_path)
    shard = ck.shard_path(out, 3, 1)
    with open(shard, "rb") as f:
        data = f.read()
    with open(shard, "wb") as f:
        f.write(data[:len(data) // 2])
    X, y = _problem()
    params = dict(BASE, output_model=out, snapshot_resume=True,
                  elastic_resume=True)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5,
                    verbose_eval=False, fobj=_int_fobj)
    assert bst.model_to_string(-1) == serial5
    assert not counters.events("elastic_resume"), \
        "the torn set was elastically loaded"
    skips = counters.events("checkpoint_skipped")
    assert skips and any(e["iteration"] == 3 for e in skips)


# ------------------------------------------------- headline e2e (tier-1)

def test_host_lost_heals_to_smaller_world_byte_identical(tmp_path):
    """ISSUE 14 acceptance pin: a 2-process supervised run loses rank 1's
    host mid-run (never respawns) — the supervisor shrinks to world=1
    through elastic resume and the final model is byte-identical to an
    uninterrupted run, with zero human input and every decision a
    structured obs event.  (The shared cell in scripts/fault_matrix.py
    drives the real Supervisor + 2 worker processes.)"""
    import importlib
    fm = importlib.import_module("scripts.fault_matrix")
    msg = fm._run_elastic_cell("host_lost@4:rank=1", str(tmp_path))
    assert msg == "ok", msg
    # every decision along the way is a structured event
    assert counters.events("rank_dead")
    evicted = counters.events("rank_evicted")
    assert evicted and evicted[-1]["rank"] == 1
    resizes = counters.events("world_resize")
    assert resizes and resizes[-1]["world"] == 1


def test_gspmd_host_lost_heals_to_smaller_world_byte_identical(tmp_path):
    """ISSUE 18 acceptance pin: the same unattended heal under
    multi-process GSPMD — a real 2-process compiler-owned group loses
    rank 1's host (never respawned), the supervisor evicts it, re-plans
    the mesh at world=1, and relaunches through elastic resume to the
    byte-identical uninterrupted model.  Every decision is a structured
    obs event; the cell itself verifies byte-identity against the
    uninterrupted single-process baseline."""
    import importlib
    fm = importlib.import_module("scripts.fault_matrix")
    msg = fm._run_elastic_cell("host_lost@4:rank=1!gspmd", str(tmp_path))
    assert msg == "ok", msg
    evicted = counters.events("rank_evicted")
    assert evicted and evicted[-1]["rank"] == 1
    resizes = counters.events("world_resize")
    assert resizes and resizes[-1]["world"] == 1
