"""Self-healing training (docs/ROBUSTNESS.md "Self-healing training"):
the supervisor's liveness machinery — heartbeat files, hang detection,
escalating teardown, bounded group restarts — plus THE tier-1 pins:

* kill one rank of a 2-process group mid-run → the supervisor restarts
  the whole group from the last committed set and the final model is
  byte-identical to an uninterrupted supervised run;
* wedge one rank (the hang variant) → the group recovers without human
  input: the healthy rank surfaces an in-band ``CollectiveError`` from
  the snapshot barrier (the ``hang_timeout``/``collective_timeout``
  composition) and the wedged one is SIGKILL-escalated.

The cheap unit layer (heartbeats, sweeps, budgets, composition) runs
in-process; only the two 2-process pins spawn real worker groups.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import checkpoint as ckpt
from lightgbm_tpu import supervisor as sup_mod
from lightgbm_tpu.obs.counters import counters
from lightgbm_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# ------------------------------------------------------------ heartbeat unit

def test_heartbeat_stamp_roundtrip_and_throttle(tmp_path):
    path = str(tmp_path / "m.txt.heartbeat.rank_0")
    hb = ckpt.Heartbeat(path, interval=30.0)
    hb.stamp(3, force=True)
    got = ckpt.read_heartbeat(path)
    assert got is not None
    it, age = got
    assert it == 3 and 0 <= age < 5.0
    hb.stamp(4)                      # throttled: 30s interval not elapsed
    assert ckpt.read_heartbeat(path)[0] == 3
    hb.stamp(5, force=True)          # forced stamps bypass the throttle
    assert ckpt.read_heartbeat(path)[0] == 5
    # a missing / garbled heartbeat reads as None, never raises
    assert ckpt.read_heartbeat(str(tmp_path / "nope")) is None
    with open(path, "w") as f:
        f.write("not json")
    assert ckpt.read_heartbeat(path) is None


def test_slow_heartbeat_fault_suppresses_writes(tmp_path):
    path = str(tmp_path / "m.txt.heartbeat.rank_0")
    hb = ckpt.Heartbeat(path, interval=0.0)
    faults.install("slow_heartbeat")
    hb.stamp(1, force=True)
    assert not os.path.exists(path)   # the write never landed
    faults.clear()
    hb.stamp(2, force=True)
    assert ckpt.read_heartbeat(path)[0] == 2


def test_heartbeat_zero_added_collectives(tmp_path):
    """Acceptance: heartbeats + snapshots + preemption watch armed on the
    no-failure path add ZERO host-object collectives (the PR 6 pin,
    extended over the liveness layer)."""
    rng = np.random.RandomState(0)
    X = rng.randn(300, 6)
    y = (X @ rng.randn(6) > 0).astype(np.float64)
    out = str(tmp_path / "m.txt")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "snapshot_freq": 2, "output_model": out, "telemetry": True,
               "heartbeat_interval": 0.001, "preempt_signal": "sigterm"},
              lgb.Dataset(X, label=y), num_boost_round=4,
              verbose_eval=False, resume=True)
    assert counters.get("collective_calls") == {}
    assert counters.get("collective_bytes") == {}
    got = ckpt.read_heartbeat(ckpt.heartbeat_path(out, 0))
    assert got is not None and got[0] == 4    # final forced stamp


# -------------------------------------------------------- crash report unit

def test_write_crash_report_contents(tmp_path):
    counters.reset()
    counters.event("group_restart", attempt=1)
    out = str(tmp_path / "m.txt")
    try:
        raise RuntimeError("the poisoned iteration")
    except RuntimeError as e:
        path = ckpt.write_crash_report(out, 1, exc=e)
    assert path == ckpt.crash_report_path(out, 1)
    text = open(path).read()
    assert "the poisoned iteration" in text          # exception
    assert "test_write_crash_report_contents" in text  # stack frames
    assert "group_restart" in text                   # obs event-ring tail


def test_engine_writes_crash_report_on_abnormal_exit(tmp_path):
    """A supervised rank (heartbeats armed) that dies of an exception
    leaves <output_model>.crash.rank_R behind, naming the failure."""
    rng = np.random.RandomState(1)
    X = rng.randn(300, 6)
    y = (X @ rng.randn(6) > 0).astype(np.float64)
    out = str(tmp_path / "m.txt")
    with pytest.raises(lgb.NonFiniteError):
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "heartbeat_interval": 0.001, "output_model": out,
                   "fault_inject": "nan_grad@2"},
                  lgb.Dataset(X, label=y), num_boost_round=4,
                  verbose_eval=False)
    text = open(ckpt.crash_report_path(out, 0)).read()
    assert "NonFiniteError" in text and "iteration 2" in text


# ------------------------------------------------------- startup hygiene

def test_sweep_stale_tmp_dead_pid_only(tmp_path):
    counters.reset()
    out = str(tmp_path / "m.txt")
    # a dead-pid leftover (no pid this large), a live-pid one, and noise
    dead = str(tmp_path / ".m.txt.snapshot_iter_4.rank_1.tmp.r1.999999999")
    live = str(tmp_path / f".m.txt.snapshot_iter_4.rank_0.tmp.r0.{os.getpid()}")
    other = str(tmp_path / "unrelated.txt")
    for p in (dead, live, other):
        with open(p, "w") as f:
            f.write("x")
    removed = ckpt.sweep_stale_tmp(out)
    assert removed == [dead]
    assert os.path.exists(live) and os.path.exists(other)
    evs = counters.events("stale_sweep")
    assert len(evs) == 1 and "dead pid" in evs[0]["reason"]


def test_sweep_orphan_crash_reports_and_heartbeats(tmp_path):
    out = str(tmp_path / "m.txt")
    for p in (ckpt.crash_report_path(out, 0), ckpt.heartbeat_path(out, 1)):
        with open(p, "w") as f:
            f.write("old")
    assert ckpt.sweep_stale_tmp(out) == []        # neither swept by default
    removed = ckpt.sweep_stale_tmp(out, crash_reports=True, heartbeats=True)
    assert sorted(removed) == sorted([ckpt.crash_report_path(out, 0),
                                      ckpt.heartbeat_path(out, 1)])


def test_eviction_metrics_and_artifact_hygiene(tmp_path):
    """ISSUE 18 satellite: evicting a rank updates every telemetry
    surface in ONE scrape — the evicted rank's
    ``rank_heartbeat_age_seconds`` gauge disappears (not left to age),
    ``world_size`` drops, ``rank_evicted_total`` counts — and the dead
    rank's heartbeat/crash-report files are swept from disk."""
    from lightgbm_tpu.obs import metrics as obs_metrics

    def lines(body, name):
        return [ln for ln in body.splitlines()
                if ln.startswith(obs_metrics.PREFIX + name)
                and not ln.startswith("#")]

    counters.reset()
    out = str(tmp_path / "m.txt")
    sup = sup_mod.Supervisor([sys.executable, "-c", "pass"], out, 2,
                             elastic_resume=True)
    for r in (0, 1):
        ckpt.Heartbeat(ckpt.heartbeat_path(out, r), 0.0).stamp(3,
                                                               force=True)
    with open(ckpt.crash_report_path(out, 1), "w") as f:
        f.write("boom")
    body = obs_metrics.render_prometheus()
    assert lines(body, 'rank_heartbeat_age_seconds{rank="0"}')
    assert lines(body, 'rank_heartbeat_age_seconds{rank="1"}')
    assert [float(ln.split()[-1]) for ln in lines(body, "world_size")] \
        == [2.0]
    assert [float(ln.split()[-1])
            for ln in lines(body, "rank_evicted_total")] == [0.0]

    sup._launch = lambda: None          # unit scope: no real relaunch
    assert sup._shrink(1, "rank_dead", "exit code 70") is None
    body = obs_metrics.render_prometheus()
    assert lines(body, 'rank_heartbeat_age_seconds{rank="0"}')
    assert not lines(body, 'rank_heartbeat_age_seconds{rank="1"}'), \
        "the evicted rank's heartbeat gauge survived the scrape"
    assert all(float(ln.split()[-1]) == 1.0
               for ln in lines(body, "world_size"))
    assert all(float(ln.split()[-1]) == 1.0
               for ln in lines(body, "rank_evicted_total"))
    # the dead incarnation's files went with it
    assert os.path.exists(ckpt.heartbeat_path(out, 0))
    assert not os.path.exists(ckpt.heartbeat_path(out, 1))
    assert not os.path.exists(ckpt.crash_report_path(out, 1))
    evs = counters.events("world_resize")
    assert evs and evs[-1]["world"] == 1


def test_group_resume_sweeps_stale_tmp_orphan_free(tmp_path):
    """Satellite pin: find_latest_valid_group leaves no dead-pid tmp
    leftovers behind — a crashed rank's half-written atomic tmp does not
    live forever on the shared filesystem."""
    import zlib
    out = str(tmp_path / "m.txt")
    world, fps = 2, [11, 22]

    def write_gather(it):
        def gather(payload):
            infos = []
            for r in range(world):
                p = ckpt.shard_path(out, it, r)
                if os.path.exists(p):
                    with open(p, "rb") as f:
                        infos.append({"rank": r, "crc": zlib.crc32(f.read()),
                                      "fingerprint": fps[r]})
            return infos
        return gather

    for r in (1, 0):
        ckpt.write_group_snapshot(out, 2, "tree\n" if r == 0 else "",
                                  {"version": 1, "iteration": 2, "rank": r},
                                  rank=r, world=world, fingerprint=fps[r],
                                  gather=write_gather(2))
    stale = str(tmp_path / ".m.txt.snapshot_iter_4.rank_1.tmp.r1.999999999")
    with open(stale, "w") as f:
        f.write("half")

    def resume_gather(payload):
        return [dict(zip(("ok", "fatal"),
                         ckpt._local_valid_group_iters(out, r, world,
                                                       fps[r])),
                     rank=r) for r in range(world)]

    it, _, _ = ckpt.find_latest_valid_group(out, rank=0, world=world,
                                            fingerprint=fps[0],
                                            gather=resume_gather)
    assert it == 2
    assert not os.path.exists(stale)
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp.r" in p]
    assert leftovers == []


def test_latest_committed_iteration(tmp_path):
    out = str(tmp_path / "m.txt")
    assert ckpt.latest_committed_iteration(out) is None
    ckpt.write_atomic(ckpt.snapshot_path(out, 2),
                      ckpt.encode("tree\n", {"version": 1, "iteration": 2}))
    assert ckpt.latest_committed_iteration(out) == 2
    # a torn newer snapshot does not count as progress
    torn = ckpt.encode("tree\n", {"version": 1, "iteration": 6})
    with open(ckpt.snapshot_path(out, 6), "wb") as f:
        f.write(torn[:len(torn) // 2])
    assert ckpt.latest_committed_iteration(out) == 2
    # a committed SET newer than the plain snapshot wins
    ckpt.write_atomic(ckpt.manifest_path(out, 4),
                      ckpt.encode("", {"version": 1, "iteration": 4,
                                       "process_count": 2,
                                       "shard_crc32": [0, 0],
                                       "data_fingerprint": [0, 0]}))
    assert ckpt.latest_committed_iteration(out) == 4


# --------------------------------------------------- composition + budget

def test_effective_hang_timeout_composes_with_collective_timeout():
    # unclamped when already above the ladder's worst case
    assert sup_mod.effective_hang_timeout(60.0, 1.0, 5.0, 2) == 60.0
    # clamped: collective_timeout * attempts + heartbeat_interval + 1
    assert sup_mod.effective_hang_timeout(2.0, 0.5, 5.0, 1) == \
        pytest.approx(5.0 * 2 + 0.5 + 1.0)
    # 0 = the supervisor default
    assert sup_mod.effective_hang_timeout(0.0, 1.0, None) == \
        sup_mod.DEFAULT_HANG_TIMEOUT


def test_config_validates_liveness_params():
    base = {"objective": "binary", "verbose": -1}
    d = lgb.Dataset(np.zeros((10, 2)), label=np.zeros(10))
    for bad in ({"heartbeat_interval": -1}, {"hang_timeout": -2},
                {"restart_limit": -1}, {"restart_backoff": -0.5},
                {"heartbeat_interval": 5, "hang_timeout": 2}):
        with pytest.raises(Exception):
            lgb.train(dict(base, **bad), d)


def test_fault_rank_qualifier_parse_and_config_rejection():
    es = faults.parse_spec("rank_crash@3:rank=1")
    assert es[0].point == "rank_crash" and es[0].iteration == 3 \
        and es[0].rank == 1
    for bad in ("rank_crash@3:rank=x", "rank_crash:cpu=1",
                "rank_crash:rank=-2"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)
    # config rejects a rank the job does not run
    d = lgb.Dataset(np.zeros((10, 2)), label=np.zeros(10))
    with pytest.raises(Exception, match="rank"):
        lgb.train({"objective": "binary", "verbose": -1,
                   "fault_inject": "rank_crash@3:rank=1"}, d)


def test_fault_rank_qualifier_targets_one_rank(monkeypatch):
    plan = faults.FaultPlan("rank_hang@2:rank=1,slow_heartbeat:rank=0")
    monkeypatch.setenv("LGBM_TPU_RANK", "0")
    assert not plan.fire("rank_hang", 2)
    assert plan.fire("slow_heartbeat")
    monkeypatch.setenv("LGBM_TPU_RANK", "1")
    assert plan.fire("rank_hang", 2)
    assert not plan.fire("slow_heartbeat")


# ------------------------------------------------ supervised group pins

SUP_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)      # exactly one device per process
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()
import numpy as np
import lightgbm_tpu as lgb

rank = int(os.environ["LGBM_TPU_RANK"])
first = os.environ.get("LGBM_TPU_SUPERVISOR_ATTEMPT", "0") == "0"

rng = np.random.RandomState(7)
n, f = 3000, 8
X = (rng.randint(0, 24, size=(n, f)) / 4.0).astype(np.float32)
w = rng.randn(f)
y = ((X @ w + 2.0 * rng.randn(n)) > np.median(X @ w)).astype(np.float32)
lo, hi = (0, n // 2) if rank == 0 else (n // 2, n)

params = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
              learning_rate=0.2, verbose=-1, tree_learner="data",
              num_machines=2, machine_list_file=os.environ["TEST_MLIST"],
              snapshot_freq=2, output_model=os.environ["TEST_SNAP"],
              heartbeat_interval=0.05, preempt_signal="sigterm",
              collective_timeout=5, collective_retries=0)
fault = os.environ.get("TEST_FAULT", "")
if fault and first:
    # only the FIRST incarnation is poisoned: the restarted group proves
    # the recovery (LGBM_TPU_SUPERVISOR_ATTEMPT is the supervisor's
    # restart counter)
    params["fault_inject"] = fault
bst = lgb.train(params, lgb.Dataset(X[lo:hi], label=y[lo:hi]),
                num_boost_round=6, verbose_eval=False, resume=True)
bst.save_model(os.environ["TEST_OUT"] + f".rank{rank}.txt")
print("WORKER_DONE", rank)
"""


def _run_supervised_pair(tmp_path, name, fault):
    """One supervised 2-process group under ``fault``; returns (exit code,
    rank-0 model text or None)."""
    from lightgbm_tpu.parallel import mesh
    d = tmp_path / name
    d.mkdir()
    script = tmp_path / "sup_worker.py"
    script.write_text(SUP_WORKER)
    mlist = d / "mlist.txt"
    mlist.write_text("127.0.0.1 0\n127.0.0.1 0\n")   # prelaunch rebinds
    out = str(d / "model")
    env = {"TEST_MLIST": str(mlist), "TEST_SNAP": str(d / "snap" / "m.txt"),
           "TEST_OUT": out, "TEST_FAULT": fault,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    sup = sup_mod.Supervisor(
        [sys.executable, str(script)], str(d / "snap" / "m.txt"), 2,
        heartbeat_interval=0.05, hang_timeout=60.0, restart_limit=2,
        restart_backoff=0.05, term_grace=8.0, poll_interval=0.05, env=env,
        prelaunch=lambda s: mesh.refresh_local_ports(str(mlist)))
    rc = sup.run()
    m0 = out + ".rank0.txt"
    return rc, (open(m0).read() if os.path.exists(m0) else None)


@pytest.fixture(scope="module")
def supervised_ref(tmp_path_factory):
    """The uninterrupted supervised 2-process baseline, shared by both
    group pins (and itself a pin: a clean supervised run needs zero
    restarts)."""
    counters.reset()
    rc, ref0 = _run_supervised_pair(tmp_path_factory.mktemp("sup_ref"),
                                    "ref", "")
    assert rc == 0 and ref0 is not None
    assert counters.events("rank_dead") == []
    assert counters.events("group_restart") == []
    return ref0


def test_supervisor_two_process_kill_rank1_byte_identical(tmp_path,
                                                          supervised_ref):
    """THE self-healing pin: rank 1 is killed hard (os._exit via
    `rank_crash@4:rank=1`) mid-run.  The supervisor sees the death, tears
    the group down (rank 0 surfaces a named CollectiveError from the
    iteration-4 barrier first — its crash report says so), relaunches
    both ranks, and the resumed group finishes byte-identical to an
    uninterrupted supervised run — no human input anywhere."""
    ref0 = supervised_ref
    counters.reset()
    rc, got0 = _run_supervised_pair(tmp_path, "crash",
                                    "rank_crash@4:rank=1")
    assert rc == 0, "supervisor did not heal the group"
    dead = counters.events("rank_dead")
    assert dead and dead[0]["rank"] == 1 and dead[0]["exit_code"] == 70
    assert counters.events("group_restart")
    # rank 0 died in-band (CollectiveError from the commit barrier after
    # its peer vanished) and left a crash report saying so
    reports = counters.events("crash_report")
    assert any(e["rank"] == 0 for e in reports)
    assert got0 is not None and got0 == ref0, \
        "self-healed 2-process model differs from uninterrupted run"
    crash_out = str(tmp_path / "crash" / "model") + ".rank1.txt"
    assert open(crash_out).read() == ref0         # both ranks agree


def test_supervisor_two_process_hang_variant_recovers(tmp_path,
                                                      supervised_ref):
    """The hang variant: rank 1 wedges (`rank_hang@4:rank=1` — heartbeats
    stop, the stand-in for a stuck device collective).  Recovery needs no
    human: the healthy rank's snapshot barrier surfaces an in-band
    CollectiveError after collective_timeout (the hang_timeout
    composition — exit-code liveness catches it), the wedged rank ignores
    SIGTERM and is SIGKILL-escalated, and the restarted group completes
    byte-identical to the uninterrupted run.  (The heartbeat-side
    hang_timeout verdict itself is pinned single-process by the
    fault-matrix `rank_hang@3` cell in the tier-1 fast subset.)"""
    ref0 = supervised_ref
    counters.reset()
    rc, got0 = _run_supervised_pair(tmp_path, "hang", "rank_hang@4:rank=1")
    assert rc == 0, "supervisor did not heal the hung group"
    assert counters.events("rank_dead") or counters.events("rank_hang")
    assert counters.events("group_restart")
    assert got0 is not None and got0 == ref0, \
        "hang-recovered 2-process model differs from uninterrupted run"


def test_supervisor_restart_budget_exhausted(tmp_path):
    """A crash loop with no forward progress must give up cleanly: bare
    `rank_crash` kills every incarnation at its first boundary, so after
    restart_limit restarts the supervisor emits restart_budget_exhausted
    and returns nonzero instead of flapping forever."""
    counters.reset()
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ.pop('XLA_FLAGS', None)\n"
        "from lightgbm_tpu.utils.cache import enable_persistent_cache\n"
        "enable_persistent_cache()\n"
        "import numpy as np\n"
        "import lightgbm_tpu as lgb\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.randn(200, 5)\n"
        "y = (X @ rng.randn(5) > 0).astype(np.float64)\n"
        "lgb.train({'objective': 'binary', 'num_leaves': 4, 'verbose': -1,\n"
        "           'snapshot_freq': 2,\n"
        "           'output_model': os.environ['OUT'],\n"
        "           'heartbeat_interval': 0.05,\n"
        "           'fault_inject': 'rank_crash'},\n"
        "          lgb.Dataset(X, label=y), num_boost_round=6,\n"
        "          verbose_eval=False, resume=True)\n")
    out = str(tmp_path / "run" / "m.txt")
    env = {"OUT": out,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    sup = sup_mod.Supervisor([sys.executable, str(script)], out, 1,
                             heartbeat_interval=0.05, hang_timeout=60.0,
                             restart_limit=1, restart_backoff=0.05,
                             term_grace=2.0, poll_interval=0.05, env=env)
    rc = sup.run()
    assert rc != 0
    evs = counters.events("restart_budget_exhausted")
    assert len(evs) == 1 and evs[0]["limit"] == 1
    assert len(counters.events("rank_dead")) == 2   # every incarnation died
    assert len(counters.events("group_restart")) == 1


def test_supervisor_startup_sweep_is_orphan_free(tmp_path):
    """Satellite pin: supervisor launch sweeps a previous job's leftovers
    (dead-pid tmps, orphan crash reports, stale heartbeats) before the
    first spawn."""
    counters.reset()
    out = str(tmp_path / "m.txt")
    stale = str(tmp_path / ".m.txt.snapshot_iter_2.rank_0.tmp.r0.999999999")
    for p in (stale, ckpt.crash_report_path(out, 0),
              ckpt.heartbeat_path(out, 0)):
        with open(p, "w") as f:
            f.write("old")
    # a worker that exits immediately: the run is about the sweep
    script = tmp_path / "noop.py"
    script.write_text("")
    sup = sup_mod.Supervisor([sys.executable, str(script)], out, 1,
                             poll_interval=0.02)
    assert sup.run() == 0
    for p in (stale, ckpt.crash_report_path(out, 0),
              ckpt.heartbeat_path(out, 0)):
        assert not os.path.exists(p), p
    assert len(counters.events("stale_sweep")) >= 3
