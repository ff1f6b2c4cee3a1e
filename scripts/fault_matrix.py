"""Run the fault-injection matrix (each fault x each recovery policy) as a
one-command smoke: every cell trains a tiny deterministic model on CPU with
one injected fault and asserts the *expected* outcome — completion with a
structured recovery event, a clean error naming the failure, or (for the
torn-checkpoint cell) a crash followed by a byte-identical resume.

    python scripts/fault_matrix.py            # full matrix
    python scripts/fault_matrix.py --fast     # tier-1 subset (the same
                                              # cells tests/test_robustness.py
                                              # runs via run_matrix(fast=True))

Exit status is non-zero if any cell deviates, printing the PASS/FAIL table
either way.  See docs/ROBUSTNESS.md for the fault point and policy
vocabulary.
"""
from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

POLICIES = ("raise", "rollback", "clamp")
FAULTS = ("none", "nan_grad@2", "inf_hess@2", "hist_fail_once",
          "torn_checkpoint@4", "collective_fail_once", "preempt@2",
          "torn_shard_rank@4", "torn_manifest@4", "rank_crash_in_barrier@4",
          "rank_crash@3", "rank_hang@3", "slow_heartbeat", "rank_crash",
          "stale_rejoin", "host_lost@4:rank=1", "host_lost@4:rank=1!strict",
          "host_lost@4:rank=1!gspmd", "rank_hang@4:rank=1!gspmd",
          "host_lost@4:rank=1!gspmd_planfail")
# multi-process snapshot-set faults: protocol-level cells driven through a
# simulated 2-rank group (sequential ranks + a disk-backed gather stub, the
# tests/test_robustness.py harness); expected outcomes below.  They do not
# interact with nonfinite_policy, so only the `raise` column runs them.
MP_FAULTS = ("torn_shard_rank@4", "torn_manifest@4",
             "rank_crash_in_barrier@4")
# self-healing supervisor cells (docs/ROBUSTNESS.md "Self-healing
# training"): each runs a real supervised worker process through
# lightgbm_tpu.supervisor with one liveness fault and asserts the
# supervisor's verdict — automatic recovery to the byte-identical
# uninterrupted model, or a clean restart_budget_exhausted give-up for
# the crash-loop cell (bare `rank_crash` dies at the first boundary of
# EVERY incarnation, so no forward progress ever refills the budget).
# Policy-blind like the MP cells: only the `raise` column runs them.
SUP_FAULTS = {                       # fault -> expected supervisor outcome
    "rank_crash@3": "recovered",     # hard death -> rank_dead -> restart
    "rank_hang@3": "recovered",      # wedged rank -> rank_hang via
    #                                  hang_timeout -> SIGKILL escalation
    "slow_heartbeat": "recovered",   # heartbeats never land: a live rank
    #                                  looks dead -> false-positive restart
    #                                  still converges
    "rank_crash": "budget_exhausted",
}
# elastic-group cells (docs/ROBUSTNESS.md "Elastic groups"): a REAL
# 2-process supervised group loses rank 1's host mid-run (``host_lost``
# kills it at boundary 4 and every relaunch dies before its first
# heartbeat — the host is not coming back).  With ``elastic_resume`` the
# supervisor declares the host lost after ``world_shrink_after``
# consecutive startup failures and relaunches at world=1 through the
# elastic-resume path; the shrunk-world model must be byte-identical to
# an uninterrupted single-process run.  The ``!strict`` variant is the
# SAME fault with elastic healing off: the correct outcome is a clean
# restart_budget_exhausted give-up, never a silent shrink.  Policy-blind
# like the SUP cells: only the `raise` column runs them.
ELASTIC_FAULTS = {                   # fault -> expected supervisor outcome
    "host_lost@4:rank=1": "shrunk",
    "host_lost@4:rank=1!strict": "budget_exhausted",
    # the gspmd-vs-shardmap elastic parity cells: the bare cells above pin
    # the shard_map path explicitly (parallel_impl=shardmap), the !gspmd
    # variants run the SAME supervised group through the compiler-owned
    # path — host_lost must shrink to the byte-identical model, a wedged
    # GSPMD collective must surface as a hang_timeout verdict and restart
    # (never a silent hang), and a shrink the mesh planner refuses must
    # exit with a structured mesh_plan_failed, never a compile-time OOM
    "host_lost@4:rank=1!gspmd": "shrunk",
    "rank_hang@4:rank=1!gspmd": "recovered",
    "host_lost@4:rank=1!gspmd_planfail": "mesh_plan_refused",
}
# the ~2-minute tier loop runs this subset (tests/test_robustness.py)
FAST_CELLS = {("none", "raise"), ("nan_grad@2", "raise"),
              ("nan_grad@2", "rollback"), ("torn_checkpoint@4", "raise"),
              ("collective_fail_once", "raise"), ("preempt@2", "raise"),
              ("torn_shard_rank@4", "raise"), ("torn_manifest@4", "raise"),
              ("rank_crash_in_barrier@4", "raise"),
              ("rank_crash@3", "raise"), ("rank_hang@3", "raise"),
              ("rank_crash", "raise"), ("stale_rejoin", "raise"),
              ("host_lost@4:rank=1!gspmd_planfail", "raise")}


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(400, 8)
    w = rng.randn(8)
    y = (X @ w + 0.3 * rng.randn(400) > 0).astype(np.float64)
    return X, y


def _run_cell(fault: str, policy: str, X, y, workdir: str) -> str:
    """Run one cell; returns "ok" or a failure description."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.counters import counters
    from lightgbm_tpu.parallel import sync
    from lightgbm_tpu.utils import faults
    from lightgbm_tpu.utils.faults import InjectedFault, SimulatedCrash

    out = os.path.join(workdir, f"{fault}_{policy}".replace("@", "_"),
                       "m.txt")
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "nonfinite_policy": policy, "telemetry": True,
              "snapshot_freq": 2, "output_model": out}

    def train(extra=None, resume=False):
        p = dict(params, **(extra or {}))
        return lgb.train(p, lgb.Dataset(X, label=y, free_raw_data=False),
                         num_boost_round=6, verbose_eval=False,
                         resume=resume or None)

    try:
        if fault == "none":
            bst = train()
            if counters.events("nonfinite"):
                return "unexpected nonfinite event on clean run"
            if not np.isfinite(bst.predict(X, raw_score=True)).all():
                return "non-finite prediction on clean run"
            return "ok"

        if fault in ("nan_grad@2", "inf_hess@2"):
            try:
                bst = train({"fault_inject": fault})
            except lgb.NonFiniteError as e:
                if policy != "raise":
                    return f"policy={policy} raised: {e}"
                return "ok" if "iteration 2" in str(e) \
                    else f"error does not name the iteration: {e}"
            if policy == "raise":
                return "raise policy completed silently"
            evs = counters.events("nonfinite")
            if len(evs) != 1:
                return f"expected exactly 1 nonfinite event, got {len(evs)}"
            if not np.isfinite(bst.predict(X, raw_score=True)).all():
                return "recovered model is non-finite"
            return "ok"

        if fault == "hist_fail_once":
            try:
                train({"fault_inject": fault})
                return "hist_fail did not surface"
            except InjectedFault:
                return "ok"

        if fault == "torn_checkpoint@4":
            ref = train().inner.save_model_to_string(-1)
            out2 = os.path.join(os.path.dirname(out), "crash", "m.txt")
            try:
                train({"fault_inject": fault, "output_model": out2})
                return "torn_checkpoint did not crash"
            except SimulatedCrash:
                pass
            bst = train({"output_model": out2}, resume=True)
            return "ok" if bst.inner.save_model_to_string(-1) == ref \
                else "resumed model differs from uninterrupted run"

        if fault == "preempt@2":
            # expected: clean loop exit at iteration 2 with a valid
            # checkpoint; resume completes to the byte-identical
            # uninterrupted model
            ref = train().inner.save_model_to_string(-1)
            out2 = os.path.join(os.path.dirname(out), "preempt", "m.txt")
            bst = train({"fault_inject": fault, "output_model": out2})
            if bst.current_iteration() != 2:
                return f"stopped at {bst.current_iteration()}, expected 2"
            from lightgbm_tpu import checkpoint as ck
            if not os.path.exists(ck.snapshot_path(out2, 2)):
                return "no preemption checkpoint on disk"
            bst2 = train({"output_model": out2}, resume=True)
            return "ok" if bst2.inner.save_model_to_string(-1) == ref \
                else "preempt-resumed model differs from uninterrupted run"

        if fault in MP_FAULTS:
            return _run_mp_cell(fault, workdir)

        if fault in SUP_FAULTS:
            return _run_sup_cell(fault, X, y, workdir)

        if fault == "collective_fail_once":
            faults.install("collective_fail_once")
            try:
                got = sync.allgather_object({"probe": policy})
                if got != [{"probe": policy}]:
                    return f"allgather returned {got!r}"
                retries = counters.get("collective_retries")
                return "ok" if retries else "retry was not counted"
            finally:
                faults.clear()

        if fault == "stale_rejoin":
            # incarnation epoch fence: a process from a DEAD incarnation
            # sends one frame into the current group.  Expected outcome
            # (policy-blind, so all three columns pin the same contract):
            # a terminal StaleEpochError naming BOTH epochs, no retry
            # burned (retrying cannot make a stale process current), and
            # a structured stale_epoch_rejected event.
            from lightgbm_tpu.checkpoint import GROUP_EPOCH_ENV
            counters.reset()
            os.environ[GROUP_EPOCH_ENV] = "3"
            faults.install("stale_rejoin")
            try:
                sync.allgather_object({"probe": policy})
                return "the stale frame was not rejected"
            except sync.StaleEpochError as e:
                if e.frame_epoch != 2 or e.group_epoch != 3:
                    return f"wrong epochs on the error: {e!r}"
                if "epoch 2" not in str(e) or "epoch 3" not in str(e):
                    return f"error does not name both epochs: {e}"
                if counters.get("collective_retries"):
                    return "the stale frame burned a retry (the fence " \
                           "must be terminal)"
                if not counters.events("stale_epoch_rejected"):
                    return "no stale_epoch_rejected event"
                return "ok"
            finally:
                faults.clear()
                os.environ.pop(GROUP_EPOCH_ENV, None)

        if fault in ELASTIC_FAULTS:
            return _run_elastic_cell(fault, workdir)

        return f"unknown fault {fault!r}"
    except Exception as e:   # noqa: BLE001 - the matrix reports, not raises
        return f"unexpected {type(e).__name__}: {e}"


def _run_mp_cell(fault: str, workdir: str) -> str:
    """One simulated 2-rank snapshot-set cell.  Expected outcomes:

    * ``torn_shard_rank@4``      — rank 1 dies tearing its shard; no
      iteration-4 manifest is ever committed; the group resumes from 2.
    * ``torn_manifest@4``        — rank 0 dies mid-manifest; the torn
      manifest fails its CRC; the group resumes from 2.
    * ``rank_crash_in_barrier@4`` — a rank dies between shard write and
      barrier; nothing commits; the group resumes from 2.
    """
    import zlib

    from lightgbm_tpu import checkpoint as ck
    from lightgbm_tpu.utils import faults
    from lightgbm_tpu.utils.faults import SimulatedCrash

    world, fps = 2, [11, 22]
    out = os.path.join(workdir, fault.replace("@", "_"), "m.txt")

    def write_gather(it):
        def gather(payload):
            infos = []
            for r in range(world):
                p = ck.shard_path(out, it, r)
                if os.path.exists(p):
                    with open(p, "rb") as f:
                        infos.append({"rank": r, "crc": zlib.crc32(f.read()),
                                      "fingerprint": fps[r]})
            return infos
        return gather

    def resume_gather(payload):
        return [dict(zip(("ok", "fatal"),
                         ck._local_valid_group_iters(out, r, world, fps[r])),
                     rank=r) for r in range(world)]

    def write_set(it, ranks=(1, 0)):
        for r in ranks:
            ck.write_group_snapshot(
                out, it, "tree\n" if r == 0 else "",
                {"version": 1, "iteration": it, "rank": r},
                rank=r, world=world, fingerprint=fps[r],
                gather=write_gather(it))

    write_set(2)                      # the previous good set
    faults.install(fault)
    crashed = False
    try:
        # torn_shard_rank must hit a NON-zero rank (rank 1 writes first in
        # the simulation); the barrier crash is exercised on rank 0
        write_set(4, ranks=((0,) if "barrier" in fault else (1, 0)))
    except SimulatedCrash:
        crashed = True
    finally:
        faults.clear()
    if not crashed:
        return f"{fault} did not crash the snapshot write"
    if fault != "torn_manifest@4" and \
            os.path.exists(ck.manifest_path(out, 4)):
        return "a manifest was committed despite the crash"
    for r in range(world):
        got = ck.find_latest_valid_group(out, rank=r, world=world,
                                         fingerprint=fps[r],
                                         gather=resume_gather)
        if got is None or got[0] != 2:
            return (f"rank {r} resumed from "
                    f"{None if got is None else got[0]}, expected set 2")
    return "ok"


# the supervised worker: deterministic single-rank training, fault armed
# through the environment — FAULT_ALWAYS=1 re-arms it in every incarnation
# (the crash-loop cell); otherwise only the FIRST incarnation is poisoned
# (LGBM_TPU_SUPERVISOR_ATTEMPT, set by the supervisor) so the restarted
# group can prove recovery.
SUP_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()   # warm grower compiles across incarnations —
#                             an iteration that recompiles from scratch
#                             every restart would dwarf the hang timeouts
#                             these cells probe
import lightgbm_tpu as lgb

d = np.load(os.environ["SUP_DATA"])
params = dict(objective="binary", num_leaves=4, verbose=-1,
              snapshot_freq=2, output_model=os.environ["SUP_OUT"],
              heartbeat_interval=0.05, preempt_signal="sigterm")
first = os.environ.get("LGBM_TPU_SUPERVISOR_ATTEMPT", "0") == "0"
fault = os.environ.get("SUP_FAULT", "")
if fault and (first or os.environ.get("SUP_FAULT_ALWAYS") == "1"):
    params["fault_inject"] = fault
bst = lgb.train(params, lgb.Dataset(d["X"], label=d["y"],
                                    free_raw_data=False),
                num_boost_round=6, verbose_eval=False, resume=True)
if "slow_heartbeat" in params.get("fault_inject", ""):
    # the poisoned incarnation must outlive the hang timeout: its
    # boundary stamps never landed, so a rank that is alive and done
    # LOOKS wedged to file-based liveness — linger until the
    # false-positive verdict fires and the supervisor kills us
    import time
    time.sleep(60)
bst.save_model(os.environ["SUP_OUT"])
"""

_SUP_REF = {}     # workdir -> uninterrupted supervised model text


def _run_supervised(fault: str, workdir: str, out: str, *,
                    always: bool = False, hang_timeout: float = 1.0,
                    startup_grace: float = 60.0, restart_limit: int = 3):
    """One supervised run; returns the Supervisor's exit code."""
    from lightgbm_tpu.supervisor import Supervisor
    script = os.path.join(workdir, "sup_worker.py")
    data = os.path.join(workdir, "sup_data.npz")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(SUP_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {"SUP_DATA": data, "SUP_OUT": out, "SUP_FAULT": fault,
           "SUP_FAULT_ALWAYS": "1" if always else "",
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    sup = Supervisor([sys.executable, script], out, 1,
                     heartbeat_interval=0.05, hang_timeout=hang_timeout,
                     startup_grace=startup_grace,
                     restart_limit=restart_limit, restart_backoff=0.05,
                     term_grace=1.0, poll_interval=0.05, env=env)
    return sup.run()


def _run_sup_cell(fault: str, X, y, workdir: str) -> str:
    """One self-healing supervisor cell (expected outcomes: SUP_FAULTS)."""
    import numpy as np

    from lightgbm_tpu.obs.counters import counters

    data = os.path.join(workdir, "sup_data.npz")
    if not os.path.exists(data):
        np.savez(data, X=np.asarray(X[:200], np.float64),
                 y=np.asarray(y[:200], np.float64))
    if workdir not in _SUP_REF:       # uninterrupted supervised baseline
        ref_out = os.path.join(workdir, "sup_ref", "m.txt")
        # generous hang timeout: this run may pay the cold grower compile
        # (and warms the persistent cache for every cell after it)
        if _run_supervised("", workdir, ref_out, hang_timeout=60.0) != 0:
            return "uninterrupted supervised baseline failed"
        with open(ref_out) as f:
            _SUP_REF[workdir] = f.read()
    counters.reset()
    out = os.path.join(workdir, "sup_" + fault.replace("@", "_"), "m.txt")
    expect = SUP_FAULTS[fault]
    # slow_heartbeat is armed per-boundary (@1..@6) so the forced stamp at
    # train entry still LANDS: the cell then exercises the stale-file
    # verdict deterministically (the file exists, then goes silent while
    # the rank lingers alive) instead of racing the jax-import window
    # against the startup grace
    spec = fault if fault != "slow_heartbeat" else ",".join(
        f"slow_heartbeat@{k}" for k in range(1, 7))
    rc = _run_supervised(
        spec, workdir, out,
        always=(expect == "budget_exhausted"),
        restart_limit=(1 if expect == "budget_exhausted" else 3),
        # hang verdicts need a timeout above the (cache-warm) iteration
        # cost but low enough to keep the cell quick; crash verdicts ride
        # exit codes and never consult it
        hang_timeout=(6.0 if fault in ("rank_hang@3", "slow_heartbeat")
                      else 60.0))
    if expect == "budget_exhausted":
        if rc == 0:
            return "crash loop completed instead of exhausting the budget"
        if not counters.events("restart_budget_exhausted"):
            return "no restart_budget_exhausted event"
        return "ok"
    if rc != 0:
        return f"supervisor gave up (exit {rc}) instead of recovering"
    want_event = "rank_hang" if fault in ("rank_hang@3",
                                          "slow_heartbeat") else "rank_dead"
    if not counters.events(want_event):
        return f"no {want_event} event behind the recovery"
    if not counters.events("group_restart"):
        return "recovered without a group_restart event"
    with open(out) as f:
        got = f.read()
    return "ok" if got == _SUP_REF[workdir] \
        else "self-healed model differs from uninterrupted run"


# the elastic worker: rank identity, world size, incarnation epoch, and the
# fault all travel through the environment (the supervisor stamps
# LGBM_TPU_WORLD / LGBM_TPU_GROUP_EPOCH per incarnation; the cell ships the
# fault spec as EL_FAULT and the worker arms it as the ``fault_inject``
# param — on the FIRST incarnation only, except ``host_lost`` whose
# contract is precisely "dies again at startup in EVERY relaunch").  The
# data slice follows the CURRENT world: at world=2 each rank trains its
# half, at world=1 the survivor trains the union — exactly the partition
# the elastic-resume path re-splices the committed 2-rank set onto.
# Integer-valued gradients keep f32 histogram sums exact under any
# summation order, so "byte-identical across a topology change" is a
# meaningful pin.  EL_IMPL pins ``parallel_impl`` (shardmap for the legacy
# cells, gspmd for the compiler-owned parity cells).
ELASTIC_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
import numpy as np
from lightgbm_tpu.utils.cache import enable_persistent_cache
enable_persistent_cache()
import lightgbm_tpu as lgb

def int_fobj(preds, ds):
    y = np.asarray(ds.get_label(), np.float32)
    g = np.clip(np.rint(np.asarray(preds, np.float64) - y), -64, 64)
    return g.astype(np.float32), np.ones_like(g, np.float32)

rng = np.random.RandomState(7)
n, f = 1600, 8
X = (rng.randint(0, 24, size=(n, f)) / 4.0).astype(np.float32)
w = rng.randn(f)
y = np.rint((X @ w) - np.median(X @ w)).astype(np.float32)
rank = int(os.environ["LGBM_TPU_RANK"])
world = int(os.environ.get("LGBM_TPU_WORLD", "2") or 2)
lo, hi = (0, n) if world == 1 else ((0, n // 2) if rank == 0 else
                                    (n // 2, n))
params = dict(objective="regression", num_leaves=7, min_data_in_leaf=10,
              learning_rate=0.5, verbose=-1, boost_from_average=False,
              tree_learner="data", num_machines=2,
              machine_list_file=os.environ["EL_MLIST"],
              output_model=os.environ["EL_SNAP"], snapshot_freq=2,
              snapshot_resume=True, heartbeat_interval=0.05,
              collective_timeout=4, collective_retries=0)
if os.environ.get("EL_IMPL"):
    params["parallel_impl"] = os.environ["EL_IMPL"]
if os.environ.get("EL_ELASTIC") == "1":
    params["elastic_resume"] = True
fault = os.environ.get("EL_FAULT", "")
first = os.environ.get("LGBM_TPU_SUPERVISOR_ATTEMPT", "0") == "0"
if fault and (first or "host_lost" in fault):
    params["fault_inject"] = fault
bst = lgb.train(params, lgb.Dataset(X[lo:hi], label=y[lo:hi],
                                    free_raw_data=False),
                num_boost_round=6, verbose_eval=False, fobj=int_fobj)
bst.save_model(os.environ["EL_OUT"] + f".rank{rank}.txt")
"""

_ELASTIC_REF = {}    # workdir -> uninterrupted single-process model text


def _elastic_serial_ref(workdir: str) -> str:
    """The uninterrupted baseline the shrunk world must reproduce: the
    SAME problem and boosting params as ELASTIC_WORKER, single process,
    no faults."""
    if workdir not in _ELASTIC_REF:
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(7)
        n, f = 1600, 8
        X = (rng.randint(0, 24, size=(n, f)) / 4.0).astype(np.float32)
        w = rng.randn(f)
        y = np.rint((X @ w) - np.median(X @ w)).astype(np.float32)

        def int_fobj(preds, ds):
            lab = np.asarray(ds.get_label(), np.float32)
            g = np.clip(np.rint(np.asarray(preds, np.float64) - lab),
                        -64, 64)
            return g.astype(np.float32), np.ones_like(g, np.float32)

        params = dict(objective="regression", num_leaves=7,
                      min_data_in_leaf=10, learning_rate=0.5, verbose=-1,
                      boost_from_average=False)
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=6, verbose_eval=False,
                        fobj=int_fobj)
        _ELASTIC_REF[workdir] = bst.model_to_string(-1)
    return _ELASTIC_REF[workdir]


def _run_elastic_cell(fault: str, workdir: str) -> str:
    """One elastic-group cell (expected outcomes: ELASTIC_FAULTS).

    Timeline of the ``shrunk`` cells: attempt 0 loses rank 1 at boundary 4
    (after the iteration-2 set committed, before 4 commits); attempts 1-2
    die at startup before a heartbeat (``host_lost`` re-arms per
    incarnation); the supervisor evicts rank 1, pre-flights the world=1
    mesh plan, and relaunches the survivor on the union through elastic
    resume to the byte-identical uninterrupted model.

    Variants after ``!``: ``strict`` disables elastic resume (the
    supervisor must give up, never shrink); ``gspmd`` runs the group under
    the compiler-owned GSPMD grower instead of shard_map (shrink parity —
    same byte-identical pin); ``gspmd_planfail`` caps the supervisor's
    ``hbm_budget`` so the world=1 mesh pre-flight REFUSES: the run must
    end with a structured ``mesh_plan_failed`` exit, not a compile-time
    OOM in the shrunken world.  A hang fault (``rank_hang``) armed only on
    the first incarnation exercises recovery-at-same-world: the wedged
    GSPMD collective surfaces (peer CollectiveError death or heartbeat-age
    verdict), the group restarts clean, and the world-2 result still
    matches the uninterrupted baseline."""
    from lightgbm_tpu.obs.counters import counters
    from lightgbm_tpu.parallel import mesh
    from lightgbm_tpu.supervisor import Supervisor

    spec, _, variant = fault.partition("!")
    strict = variant == "strict"
    planfail = variant == "gspmd_planfail"
    impl = "gspmd" if variant.startswith("gspmd") else "shardmap"
    hang = spec.startswith("rank_hang")
    d = os.path.join(workdir, "elastic_" + (variant or "legacy")
                     + ("_hang" if hang else ""))
    os.makedirs(d, exist_ok=True)
    script = os.path.join(workdir, "elastic_worker.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(ELASTIC_WORKER)
    mlist = os.path.join(d, "mlist.txt")
    with open(mlist, "w") as f:
        f.write("127.0.0.1 0\n127.0.0.1 0\n")
    out = os.path.join(d, "model")
    snap = os.path.join(d, "snap", "m.txt")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {"EL_MLIST": mlist, "EL_SNAP": snap, "EL_OUT": out,
           "EL_ELASTIC": "" if strict else "1",
           "EL_FAULT": spec, "EL_IMPL": impl,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    counters.reset()
    sup = Supervisor(
        [sys.executable, script], snap, 2,
        heartbeat_interval=0.05, hang_timeout=60.0,
        restart_limit=(2 if strict else 3), restart_backoff=0.05,
        term_grace=8.0, poll_interval=0.05, env=env,
        prelaunch=lambda _sup: mesh.refresh_local_ports(mlist),
        elastic_resume=not strict, world_shrink_after=2,
        machine_list_file=mlist,
        hbm_budget=(1 if planfail else 0))
    rc = sup.run()
    if strict:
        if rc == 0:
            return "strict supervisor healed a lost host (must give up)"
        if not counters.events("restart_budget_exhausted"):
            return "no restart_budget_exhausted event"
        if counters.events("world_resize"):
            return "strict mode shrank the world"
        return "ok"
    if planfail:
        # the eviction decision stands (rank_evicted) but the world=1
        # layout is unplannable under the budget — the run must stop with
        # the structured refusal, never attempt the resize
        if rc == 0:
            return "supervisor completed despite an unplannable shrink"
        if not counters.events("rank_evicted"):
            return "no rank_evicted event before the refused shrink"
        if not counters.events("mesh_plan_failed"):
            return "no mesh_plan_failed event behind the refusal"
        if counters.events("world_resize"):
            return "world_resize fired despite the mesh-plan refusal"
        return "ok"
    if rc != 0:
        return f"elastic supervisor gave up (exit {rc})"
    if hang:
        # recovery at the SAME world: the wedged collective must surface
        # as a verdict (a peer's CollectiveError death or the heartbeat-
        # age hang verdict), the group restarts, and nobody is evicted
        if not (counters.events("rank_dead") or counters.events("rank_hang")):
            return "no rank_dead/rank_hang verdict behind the wedge"
        if not counters.events("group_restart"):
            return "no group_restart event after the wedged collective"
        if counters.events("world_resize"):
            return "hang recovery shrank the world (should restart at 2)"
        for r in (0, 1):
            final = out + f".rank{r}.txt"
            if not os.path.exists(final):
                return f"no final model from rank {r} after recovery"
            with open(final) as f:
                if f.read() != _elastic_serial_ref(workdir):
                    return (f"rank {r} model differs from uninterrupted "
                            "run after hang recovery")
        return "ok"
    if not counters.events("rank_evicted"):
        return "no rank_evicted event behind the shrink"
    resizes = counters.events("world_resize")
    if not resizes or resizes[-1].get("world") != 1:
        return f"world_resize missing or wrong: {resizes}"
    final = out + ".rank0.txt"
    if not os.path.exists(final):
        return "no final model from the shrunk world"
    with open(final) as f:
        got = f.read()
    return "ok" if got == _elastic_serial_ref(workdir) \
        else "shrunk-world model differs from uninterrupted run"


def run_matrix(fast: bool = False):
    """Returns (results, failures): results is {(fault, policy): msg}."""
    X, y = _data()
    results, failures = {}, []
    with tempfile.TemporaryDirectory() as workdir:
        for fault in FAULTS:
            for policy in POLICIES:
                if fast and (fault, policy) not in FAST_CELLS:
                    continue
                if policy != "raise" and (fault in MP_FAULTS
                                          or fault in SUP_FAULTS
                                          or fault in ELASTIC_FAULTS
                                          or fault == "preempt@2"):
                    continue   # checkpoint/supervisor cells are policy-blind
                msg = _run_cell(fault, policy, X, y, workdir)
                results[(fault, policy)] = msg
                if msg != "ok":
                    failures.append((fault, policy, msg))
    return results, failures


def main(argv) -> int:
    fast = "--fast" in argv
    results, failures = run_matrix(fast=fast)
    wf = max(len(f) for f, _ in results)
    print(f"{'fault':<{wf}}  {'policy':<9} result")
    for (fault, policy), msg in sorted(results.items()):
        status = "PASS" if msg == "ok" else f"FAIL: {msg}"
        print(f"{fault:<{wf}}  {policy:<9} {status}")
    print(f"\n{len(results) - len(failures)}/{len(results)} cells passed"
          + (" (fast subset)" if fast else ""))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
