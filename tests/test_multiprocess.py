"""True multi-PROCESS data-parallel training over jax.distributed — the
analogue of the reference's socket-based parallel learning
(``examples/parallel_learning``, ``application.cpp:190-224``): two worker
processes each hold their own row partition, train tree_learner=data through
the config-driven network bring-up, and must produce the identical model —
which must also match serial training on the union of the partitions."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = r"""
import os, sys
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"

rank = int(os.environ["LGBM_TPU_RANK"])
mlist = os.environ["TEST_MLIST"]
out = os.environ["TEST_OUT"]

import lightgbm_tpu as lgb
from lightgbm_tpu.config import config_from_params

if os.environ.get("TEST_MODE") == "findbin":
    # distributed FindBin vs serial fitting on identical data: mappers must
    # be bit-identical (dataset_loader.cpp:737-816 done-criterion)
    from lightgbm_tpu.parallel.mesh import init_distributed_from_config
    from lightgbm_tpu.data.dataset import construct
    import lightgbm_tpu.parallel.sync as sync
    cfg = config_from_params(dict(num_machines=2, machine_list_file=mlist,
                                  verbose=-1, max_bin=63))
    init_distributed_from_config(cfg)
    rng = np.random.RandomState(11)
    X = np.where(rng.rand(5000, 6) < 0.3, 0.0,
                 rng.randn(5000, 6)).astype(np.float32)
    X[:, 0] = rng.randint(0, 9, size=5000)          # categorical-ish ints
    y = (X.sum(1) > 0).astype(np.float32)
    ds_dist = construct(X, cfg, label=y)
    real_pc = sync.process_count
    sync.process_count = lambda: 1                  # force the serial path
    ds_serial = construct(X, cfg, label=y)
    sync.process_count = real_pc
    a = [m.feature_info_str() for m in ds_dist.bin_mappers]
    b = [m.feature_info_str() for m in ds_serial.bin_mappers]
    assert a == b, (a, b)
    assert np.array_equal(ds_dist.binned, ds_serial.binned)
    print("WORKER_OK", rank)
    sys.exit(0)

rng = np.random.RandomState(7)
n, f = 3000, 8
# discrete grid values: every partition sees the same distinct values, so
# per-process FindBin mappers are identical by construction and the
# distributed model is comparable to serial training nearly exactly
X = (rng.randint(0, 24, size=(n, f)) / 4.0).astype(np.float32)
w = rng.randn(f)
y = ((X @ w + 2.0 * rng.randn(n)) > np.median(X @ w)).astype(np.float32)

if os.environ.get("TEST_MODE") == "feature_bad":
    # contract violation: per-process partitions fed to feature-parallel
    # must be rejected loudly (differing data signatures)
    lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)
    params = dict(objective="binary", num_leaves=15, verbose=-1,
                  tree_learner="feature", num_machines=2,
                  machine_list_file=mlist)
    try:
        lgb.train(params, lgb.Dataset(X[lo:hi], label=y[lo:hi]),
                  num_boost_round=2)
    except Exception as e:
        assert "FULL identical dataset" in str(e), e
        print("WORKER_OK", rank)
        sys.exit(0)
    print("NO_ERROR: contract violation was accepted")
    sys.exit(1)

if os.environ.get("TEST_MODE") == "feature":
    # feature-parallel multi-host: every machine holds the FULL data
    # (reference feature-parallel contract); identical models required
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                  learning_rate=0.2, verbose=-1, tree_learner="feature",
                  num_machines=2, machine_list_file=mlist)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    bst.save_model(out)
    import jax
    assert jax.process_count() == 2
    print("WORKER_OK", rank)
    sys.exit(0)

if os.environ.get("TEST_MODE") == "sharedfile":
    # both ranks point at the SAME data file, not pre-partitioned: the
    # loader must give each rank a disjoint row shard
    # (dataset_loader.cpp LoadTextDataToMemory:563-607) and the ranks
    # must still agree on the model
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                  learning_rate=0.2, verbose=-1, tree_learner="data",
                  num_machines=2, machine_list_file=mlist)
    d = lgb.Dataset(os.environ["TEST_DATA"])
    if os.environ.get("TEST_EARLY") == "1":
        # constructing BEFORE train (no parallel params) must not leak an
        # unsharded dataset into distributed training — train() rebuilds
        assert d.num_data() == n
    bst = lgb.train(params, d, num_boost_round=5)
    nd = d.num_data()
    assert 0.3 * n < nd < 0.7 * n, nd     # a proper shard, not the file
    bst.save_model(out)
    print("WORKER_OK", rank)
    sys.exit(0)

if os.environ.get("TEST_MODE") == "ckpt":
    # coordinated multi-process checkpoints (docs/ROBUSTNESS.md): each rank
    # holds a row partition whose score matrix no peer can reconstruct, so
    # snapshots are per-rank shard sets committed by a rank-0 manifest
    from lightgbm_tpu.parallel.sync import CollectiveError
    from lightgbm_tpu.utils.faults import SimulatedCrash
    phase = os.environ["TEST_CKPT_PHASE"]
    snap_out = os.environ["TEST_SNAP_OUT"]
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                  learning_rate=0.2, verbose=-1, tree_learner="data",
                  num_machines=2, machine_list_file=mlist,
                  snapshot_freq=2, output_model=snap_out)
    lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)
    d = lgb.Dataset(X[lo:hi], label=y[lo:hi])
    if phase == "ref":                     # uninterrupted baseline
        lgb.train(params, d, num_boost_round=6).save_model(out)
        print("WORKER_OK", rank)
        sys.exit(0)
    if phase == "preempt":
        # rank 1 "receives" the preemption notice (deterministic fault);
        # the per-boundary flag allgather makes BOTH ranks checkpoint at
        # iteration 3 and exit the loop cleanly
        p = dict(params, preempt_signal="sigterm")
        if rank == 1:
            p["fault_inject"] = "preempt@3"
        bst = lgb.train(p, d, num_boost_round=6)
        assert bst.current_iteration() == 3, bst.current_iteration()
        print("WORKER_OK", rank)
        sys.exit(0)
    if phase == "crash":
        # kill ONE worker mid-run: rank 1 dies tearing its iteration-4
        # shard; rank 0 must surface a named CollectiveError from the
        # commit barrier (not hang), and no iteration-4 manifest may exist
        p = dict(params, collective_timeout=10, collective_retries=0)
        if rank == 1:
            p["fault_inject"] = "torn_shard_rank@4"
        try:
            lgb.train(p, d, num_boost_round=6)
        except (SimulatedCrash, CollectiveError) as e:
            print("CRASHED", type(e).__name__)
            print("WORKER_OK", rank)
            sys.stdout.flush()
            os._exit(0)      # skip atexit: a preempted pod gets no goodbye
        print("NO_CRASH")
        os._exit(1)
    if phase == "resume":                  # both ranks resume + finish
        bst = lgb.train(dict(params, snapshot_resume=True), d,
                        num_boost_round=6)
        bst.save_model(out)
        print("WORKER_OK", rank)
        sys.exit(0)
    raise SystemExit(f"unknown ckpt phase {phase}")

if os.environ.get("TEST_MODE") == "obs_parity":
    # the zero-added-collectives pin extended over multi-process GSPMD
    # (ISSUE 18): arming the full observability plane (telemetry + flight
    # recorder + heartbeats) must add ZERO sync.py host-object collectives
    # and ZERO new compiled-HLO collective ops to the training program —
    # both compared armed-vs-unarmed inside the live 2-process group
    from lightgbm_tpu.obs.counters import counters
    lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)
    base = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                learning_rate=0.2, verbose=-1, tree_learner="data",
                num_machines=2, machine_list_file=mlist,
                parallel_impl="gspmd")

    def run(extra):
        d = lgb.Dataset(X[lo:hi], label=y[lo:hi], free_raw_data=False)
        return lgb.train(dict(base, **extra), d, num_boost_round=3,
                         verbose_eval=False)

    run({"output_model": out + ".warm"})   # absorbs the one-time
                                           # distributed bring-up traffic
    counters.reset()
    bst_plain = run({"output_model": out + ".plain"})
    plain_calls = dict(counters.get("collective_calls"))
    plain_census = bst_plain.inner.grow_hlo_census(label="parity")
    counters.reset()
    bst_armed = run({"output_model": out + ".armed", "telemetry": True,
                     "obs_stream_path": os.environ["TEST_STREAM"],
                     "heartbeat_interval": 0.01})
    armed_calls = dict(counters.get("collective_calls"))
    armed_census = bst_armed.inner.grow_hlo_census(label="parity")
    assert armed_calls == plain_calls, (plain_calls, armed_calls)
    assert armed_census == plain_census, (plain_census, armed_census)
    print("WORKER_OK", rank)
    sys.exit(0)

# this process's row partition (pre-partitioned parallel learning)
lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)

learner = "voting" if os.environ.get("TEST_MODE") == "voting" else "data"
params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
              learning_rate=0.2, verbose=-1, tree_learner=learner,
              num_machines=2, machine_list_file=mlist)
d = lgb.Dataset(X[lo:hi], label=y[lo:hi])
bst = lgb.train(params, d, num_boost_round=5)
bst.save_model(out)
# regression: boost-from-average must sync the GLOBAL label mean — the
# partitions have different local means, so identical models across ranks
# prove GlobalSyncUpByMean
yr = (X @ w).astype(np.float32) + np.linspace(0, 3, n, dtype=np.float32)
pr = dict(params, objective="regression", num_leaves=7)
dr = lgb.Dataset(X[lo:hi], label=yr[lo:hi])
bstr = lgb.train(pr, dr, num_boost_round=2)
bstr.save_model(out + ".reg")
import jax
assert jax.process_count() == 2, jax.process_count()
print("WORKER_OK", rank)
"""


def _make_grid_problem():
    """Shared dataset: discrete grid so per-process mappers are identical."""
    rng = np.random.RandomState(7)
    n, f = 3000, 8
    X = (rng.randint(0, 24, size=(n, f)) / 4.0).astype(np.float32)
    w = rng.randn(f)
    y = ((X @ w + 2.0 * rng.randn(n)) > np.median(X @ w)).astype(np.float32)
    return X, y


def _run_workers(tmp_path, mode=None, extra_env=None):
    """Spawn the 2-process worker pair; returns per-rank stdout after
    asserting both exited 0 with WORKER_OK."""
    port = _free_port()
    mlist = tmp_path / "mlist.txt"
    # reference machine-list format: "ip port" per line
    mlist.write_text(f"127.0.0.1 {port}\n127.0.0.1 {port + 1}\n")
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        # the worker script lives in tmp_path, so sys.path[0] is NOT the
        # repo — make the package importable without requiring an install
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env.update(LGBM_TPU_RANK=str(rank), TEST_MLIST=str(mlist),
                   TEST_OUT=str(tmp_path / f"model_{rank}.txt"),
                   JAX_PLATFORMS="cpu")
        if mode is not None:
            env["TEST_MODE"] = mode
        if extra_env:
            env.update(extra_env)
        env.pop("XLA_FLAGS", None)   # exactly one device per process
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True,
                                      env=env))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"multiprocess worker hung (mode={mode})")
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
        assert f"WORKER_OK {rank}" in out
    return outs


def _serial_baseline():
    import lightgbm_tpu as lgb
    X, y = _make_grid_problem()
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                  learning_rate=0.2, verbose=-1)
    return X, lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_data_parallel(tmp_path):
    _run_workers(tmp_path)
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "processes disagreed on the trained model"
    r0 = (tmp_path / "model_0.txt.reg").read_text()
    r1 = (tmp_path / "model_1.txt.reg").read_text()
    assert r0 == r1, "regression init (boost_from_average) diverged"

    # cross-check against serial training on the UNION of the partitions:
    # mappers are identical by construction (discrete grid), so the
    # data-parallel trees must match serial training up to fp reduction order
    import lightgbm_tpu as lgb
    X, bst = _serial_baseline()
    dist = lgb.Booster(model_str=m0)
    np.testing.assert_allclose(dist.predict(X[:500]), bst.predict(X[:500]),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_feature_parallel(tmp_path):
    """Feature-parallel across processes with full replicated data: both
    ranks must produce the identical model, equal to serial training."""
    _run_workers(tmp_path, mode="feature")
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1

    import lightgbm_tpu as lgb
    X, bst = _serial_baseline()
    dist = lgb.Booster(model_str=m0)
    np.testing.assert_allclose(dist.predict(X[:500]), bst.predict(X[:500]),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_shared_file_distributes_rows(tmp_path):
    """Both ranks load the SAME data file with tree_learner=data and no
    pre-partitioning: the loader hands each rank a disjoint shard (the
    worker asserts its local row count) and training still produces one
    agreed model ~ equal to serial training on the full file."""
    X, y = _make_grid_problem()
    data_path = tmp_path / "shared.tsv"
    np.savetxt(data_path, np.column_stack([y, X]), delimiter="\t",
               fmt="%.6g")
    _run_workers(tmp_path, mode="sharedfile",
                 extra_env={"TEST_DATA": str(data_path)})
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "ranks disagreed on the shared-file model"

    # same flow with an eager construct() before train(): the dataset must
    # be rebuilt with sharding, not reused unsharded
    early = tmp_path / "early"
    early.mkdir()
    _run_workers(early, mode="sharedfile",
                 extra_env={"TEST_DATA": str(data_path), "TEST_EARLY": "1"})
    e0 = (early / "model_0.txt").read_text()
    assert e0 == (early / "model_1.txt").read_text()
    assert e0 == m0, "early-construct path trained a different model"

    import lightgbm_tpu as lgb
    Xs, bst = _serial_baseline()
    dist = lgb.Booster(model_str=m0)
    # disjoint shards + identical mappers => summed histograms equal the
    # serial ones, so this matches serial training like the
    # pre-partitioned data-parallel test does
    np.testing.assert_allclose(dist.predict(Xs[:500]), bst.predict(Xs[:500]),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_distributed_findbin_matches_serial(tmp_path):
    """Both processes hold the SAME data: sharded-then-allgathered mappers
    must equal serially fitted ones bit-for-bit, and binning must agree."""
    _run_workers(tmp_path, mode="findbin")


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_voting_parallel(tmp_path):
    """PV-tree voting learner across process boundaries: ranks must agree
    on the model (vote compression makes serial equality approximate, so
    only cross-rank identity is asserted)."""
    _run_workers(tmp_path, mode="voting")
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "voting ranks disagreed on the trained model"
    assert m0.count("Tree=") >= 5
    r0 = (tmp_path / "model_0.txt.reg").read_text()
    r1 = (tmp_path / "model_1.txt.reg").read_text()
    assert r0 == r1, "voting regression/boost-from-average diverged"


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_feature_parallel_rejects_partitioned_data(tmp_path):
    """Feeding per-process row partitions to feature-parallel (full-data
    contract) must fail loudly, not train on inconsistent replicas."""
    _run_workers(tmp_path, mode="feature_bad")


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_crash_resume_byte_identical(tmp_path):
    """THE multi-process resumability contract (docs/ROBUSTNESS.md): kill
    one worker mid-run (rank 1 tears its iteration-4 shard and dies; rank
    0 times out in the commit barrier), resume BOTH from the last
    everywhere-committed set (iteration 2), and the final model is
    byte-identical to an uninterrupted 2-process run on every rank."""
    from lightgbm_tpu import checkpoint as ck

    snap = tmp_path / "snaps"
    snap.mkdir()
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    _run_workers(ref_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "ref", "TEST_SNAP_OUT": str(ref_dir / "m.txt")})
    ref0 = (ref_dir / "model_0.txt").read_text()
    assert ref0 == (ref_dir / "model_1.txt").read_text()

    crash_dir = tmp_path / "crash"
    crash_dir.mkdir()
    outs = _run_workers(crash_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "crash", "TEST_SNAP_OUT": str(snap / "m.txt")})
    assert any("CRASHED SimulatedCrash" in o for o in outs)
    assert any("CRASHED CollectiveError" in o for o in outs)
    # the iteration-2 set is committed; iteration 4 must have NO manifest
    # (rank 1 died before the barrier) — shards without a manifest never
    # happened
    assert os.path.exists(ck.manifest_path(str(snap / "m.txt"), 2))
    assert not os.path.exists(ck.manifest_path(str(snap / "m.txt"), 4))

    resume_dir = tmp_path / "resume"
    resume_dir.mkdir()
    _run_workers(resume_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "resume", "TEST_SNAP_OUT": str(snap / "m.txt")})
    r0 = (resume_dir / "model_0.txt").read_text()
    assert r0 == (resume_dir / "model_1.txt").read_text()
    assert r0 == ref0, "resumed 2-process model differs from uninterrupted"


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_two_process_preempt_coordinated_exit(tmp_path):
    """A preemption notice on ONE rank (deterministic `preempt@3` fault)
    must make BOTH ranks write the same coordinated checkpoint set and
    exit the loop cleanly at the same iteration — then resume to the
    uninterrupted final model."""
    from lightgbm_tpu import checkpoint as ck

    snap = tmp_path / "snaps"
    snap.mkdir()
    pre_dir = tmp_path / "pre"
    pre_dir.mkdir()
    _run_workers(pre_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "preempt", "TEST_SNAP_OUT": str(snap / "m.txt")})
    # the coordinated preemption checkpoint: a committed iteration-3 set
    man = ck.load_manifest(str(snap / "m.txt"), 3)
    assert man["process_count"] == 2
    assert len(man["shard_crc32"]) == 2

    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    _run_workers(ref_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "ref", "TEST_SNAP_OUT": str(ref_dir / "m.txt")})
    resume_dir = tmp_path / "resume"
    resume_dir.mkdir()
    _run_workers(resume_dir, mode="ckpt", extra_env={
        "TEST_CKPT_PHASE": "resume", "TEST_SNAP_OUT": str(snap / "m.txt")})
    assert (resume_dir / "model_0.txt").read_text() == \
        (ref_dir / "model_0.txt").read_text()


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SKIP_MULTIPROC") == "1",
                    reason="multiprocess test disabled")
def test_gspmd_armed_observability_adds_zero_collectives(tmp_path):
    """ISSUE 18 satellite: under live 2-process GSPMD training, arming
    telemetry + the flight recorder + heartbeats adds ZERO sync.py
    host-object collectives and ZERO new compiled-HLO collective ops —
    the workers compare an armed run against an unarmed one and fail
    themselves on any delta."""
    _run_workers(tmp_path, mode="obs_parity",
                 extra_env={"TEST_STREAM": str(tmp_path / "flight")})


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
