"""Host-object synchronization across processes — hardened.

The reference's distributed ``FindBin`` ships serialized ``BinMapper`` blobs
through its Bruck allgather (``dataset_loader.cpp:737-816``); with jax the
transport is the distributed runtime's allgather over a length-then-payload
two-phase pickle.  Block-distributed GBT work (PAPERS.md) shows workers +
collectives are exactly where distributed boosting fails in practice, so
every host-object collective here is wrapped in the same recovery ladder:

* **payload integrity** — each process ships ``[length, crc32]`` alongside
  its pickle; the receiver verifies every slice and an error names the
  *offending process index* instead of dying later in ``pickle.loads``;
* **timeout** — one attempt may block at most ``collective_timeout``
  seconds (the runtime's allgather has no deadline of its own: a dead peer
  used to hang the fleet silently);
* **bounded retry with backoff** — transient failures re-attempt up to
  ``collective_retries`` times (exponential backoff), each retry counted
  into the ``collective_retries`` obs counter and recorded as a
  ``collective_retry`` structured event, so recovery is visible, never
  silent;
* **fault injection** — the ``collective_fail`` / ``collective_corrupt``
  points (:mod:`lightgbm_tpu.utils.faults`) exercise the whole ladder on
  CPU in tier-1;
* **incarnation epoch fence** — every payload header carries the group
  epoch the sender was launched under (``LGBM_TPU_GROUP_EPOCH``, minted
  per (re)launch by the supervisor).  A frame from a PREVIOUS incarnation
  — a process that survived a teardown and tries to rejoin after the
  group relaunched, possibly at a different world size — raises
  :class:`StaleEpochError` naming both epochs.  The fence is terminal:
  a stale peer does not become current by retrying, so the retry ladder
  passes it straight through.  The ``stale_rejoin`` fault point replays
  exactly this on CPU at world=1 (zero hangs).

``broadcast_object`` is a real rank-0 length-then-payload broadcast: only
process 0 pickles and ships its object (it used to run a full allgather
and take element 0 — every process pickled and shipped a payload that was
thrown away).

The coordinated-checkpoint protocol (:mod:`lightgbm_tpu.checkpoint`) rides
``allgather_object`` for both of its rendezvous — the shard-CRC commit
barrier and the resume agreement — so a rank that dies mid-snapshot
surfaces as a named ``CollectiveError`` after ``collective_timeout``
seconds on its peers, never a silent fleet hang.

Division of labor under GSPMD (``parallel/gspmd.py``,
docs/DISTRIBUTED.md): the NamedSharding learners hand the DATA-plane
collectives (histogram reductions, split agreement) to the XLA
partitioner, but this module stays load-bearing as the CONTROL plane —
bin finding, checkpoint barriers, resume agreement and preemption
coordination are host-object exchanges that must survive peers dying
mid-call, which is exactly what the ladder above provides and a compiled
collective cannot.
"""
from __future__ import annotations

import pickle
import time
import zlib
from typing import Any, Callable, List, Optional

import numpy as np

from ..utils import faults as faults_mod
from ..utils import log

# module defaults; engine.train() re-configures them from params
_TIMEOUT = 120.0
_RETRIES = 2
_BACKOFF = 0.25     # seconds; doubles per retry


class CollectiveError(RuntimeError):
    """A host-object collective failed after exhausting its retries."""


class StaleEpochError(CollectiveError):
    """A collective frame arrived from a DEAD incarnation of the group.

    Carries both sides of the fence: ``frame_epoch`` (what the stale
    sender was launched under) and ``group_epoch`` (what this process was
    launched under).  Terminal by design — :func:`_retrying` never
    re-attempts it, because a process from a previous incarnation cannot
    become current by waiting; it must be swept."""

    def __init__(self, msg: str, *, frame_epoch: int, group_epoch: int):
        super().__init__(msg)
        self.frame_epoch = int(frame_epoch)
        self.group_epoch = int(group_epoch)


def _group_epoch() -> int:
    # function-local import: checkpoint.py reaches back into this module
    # (function-locally) for the resume barriers
    from ..checkpoint import group_epoch
    return group_epoch()


def _check_frame_epoch(frame_epoch: int, what: str, peer: Any = "?") -> None:
    """The incarnation fence itself: reject any frame whose stamped epoch
    differs from ours, with a structured event + error naming BOTH epochs
    and the offending process."""
    mine = _group_epoch()
    if int(frame_epoch) == mine:
        return
    from ..obs.counters import counters
    counters.event("stale_epoch_rejected", op=what, peer=str(peer),
                   frame_epoch=int(frame_epoch), group_epoch=mine)
    log.warning("%s: rejected frame from process %s at incarnation epoch "
                "%d (this group is epoch %d)", what, peer,
                int(frame_epoch), mine)
    raise StaleEpochError(
        f"{what}: frame from process {peer} carries incarnation epoch "
        f"{int(frame_epoch)} but this group is epoch {mine} — a process "
        "from a dead incarnation tried to rejoin; terminate it (it will "
        "not become current by retrying)",
        frame_epoch=int(frame_epoch), group_epoch=mine)


def _maybe_stale_rejoin(what: str) -> None:
    """``stale_rejoin`` fault point: simulate one frame from the previous
    incarnation arriving at this collective (fires BEFORE the world==1
    short-circuit so the fence is tier-1-testable with no peers)."""
    fi = faults_mod.get_faults()
    if fi.enabled and fi.fire("stale_rejoin"):
        _check_frame_epoch(_group_epoch() - 1, what, peer="injected-stale")


def configure(timeout: Optional[float] = None,
              retries: Optional[int] = None) -> None:
    """Set the module-wide timeout/retry budget (collective_timeout /
    collective_retries params; engine.train wires them per training)."""
    global _TIMEOUT, _RETRIES
    if timeout is not None:
        _TIMEOUT = float(timeout)
    if retries is not None:
        _RETRIES = int(retries)


def process_count() -> int:
    """Number of participating processes; 1 when the distributed runtime is
    not initialized (safe to call before backend init)."""
    import jax
    if not jax.distributed.is_initialized():
        return 1
    return jax.process_count()


def process_index() -> int:
    """This process's rank; 0 when the distributed runtime is not
    initialized (the single-process identity)."""
    import jax
    if not jax.distributed.is_initialized():
        return 0
    return jax.process_index()


def _with_timeout(fn: Callable[[], Any], timeout: float, what: str) -> Any:
    """Run ``fn`` with a deadline.  The underlying collective cannot be
    cancelled, but a named timeout beats an indefinite silent hang.

    A timed-out attempt is marked **abandoned** before the caller raises:
    the worker thread keeps running (nothing can cancel it), and when the
    collective eventually completes *late* its result is dropped — and
    the drop recorded as a ``collective_late_completion`` obs event —
    instead of mutating the result box after the caller already raised
    ``CollectiveError`` (or double-counting the ``collective_calls``
    accounting through a retry that is also in flight)."""
    import threading
    out: List[Any] = []
    err: List[BaseException] = []
    lock = threading.Lock()
    abandoned = [False]

    def run():
        try:
            result = fn()
        except BaseException as e:   # re-raised on the caller thread
            with lock:
                if abandoned[0]:
                    _note_late(what, f"{type(e).__name__}: {e}")
                    return
                err.append(e)
            return
        with lock:
            if abandoned[0]:
                _note_late(what, "completed")
                return
            out.append(result)

    t = threading.Thread(target=run, daemon=True, name=f"sync:{what}")
    t.start()
    t.join(timeout)
    with lock:
        # the attempt may finish between the join timeout and this lock —
        # a result that made it into the box in time still counts
        if not out and not err:
            abandoned[0] = True
    if abandoned[0]:
        raise CollectiveError(
            f"{what} timed out after {timeout:g}s (a peer process is "
            "stuck or dead; see machine_list_file ordering for ranks)")
    if err:
        raise err[0]
    return out[0]


def _note_late(what: str, outcome: str) -> None:
    """A previously abandoned collective attempt just finished: log it and
    record the structured event (never silent — a late completion is the
    evidence that ``collective_timeout`` raced a slow peer, exactly what
    the supervisor's hang-vs-timeout composition needs to see)."""
    from ..obs.counters import counters
    counters.inc("collective_late_completions", op=what)
    counters.event("collective_late_completion", op=what, outcome=outcome)
    log.warning("%s attempt completed LATE (%s) after its timeout had "
                "already surfaced; result dropped", what, outcome)


def _retrying(what: str, attempt_fn: Callable[[], Any]) -> Any:
    """Bounded-retry ladder around one collective attempt; every retry is
    counted (obs `collective_retries`) and recorded as a structured
    `collective_retry` event."""
    from ..obs.counters import counters
    last: Optional[BaseException] = None
    for attempt in range(_RETRIES + 1):
        try:
            return attempt_fn()
        except StaleEpochError:
            # the epoch fence is terminal: a stale incarnation cannot
            # become current by retrying — surface it immediately
            raise
        except Exception as e:
            last = e
            if attempt == _RETRIES:
                break
            counters.inc("collective_retries", op=what)
            counters.event("collective_retry", op=what, attempt=attempt + 1,
                           error=str(e))
            log.warning("%s failed (attempt %d/%d): %s — retrying",
                        what, attempt + 1, _RETRIES + 1, e)
            time.sleep(_BACKOFF * (2 ** attempt))
    raise CollectiveError(
        f"{what} failed after {_RETRIES + 1} attempt(s): {last}") from last


def _maybe_inject(what: str) -> None:
    fi = faults_mod.get_faults()
    if fi.enabled and fi.fire("collective_fail"):
        raise faults_mod.InjectedFault(f"collective_fail: injected {what} "
                                       "failure")


def _maybe_corrupt(buf: np.ndarray) -> np.ndarray:
    fi = faults_mod.get_faults()
    if fi.enabled and fi.fire("collective_corrupt"):
        buf = np.array(buf, copy=True)
        flat = buf.reshape(-1)
        if flat.size:
            flat[0] ^= 0xFF      # deterministic single-byte wire corruption
    return buf


def _note(op: str, nbytes: int) -> None:
    from ..obs.counters import counters
    counters.inc("collective_calls", op=op, site="parallel/sync")
    counters.inc("collective_bytes", value=nbytes, op=op,
                 site="parallel/sync")


def allgather_object(obj: Any) -> List[Any]:
    """Gather one picklable host object from every process, in process-index
    order (Network::Allgather of serialized blobs) — with length+CRC
    payload verification, per-attempt timeout, and bounded retry."""

    def attempt() -> List[Any]:
        _maybe_inject("allgather_object")
        _maybe_stale_rejoin("allgather_object")
        if process_count() == 1:
            return [obj]
        from jax.experimental import multihost_utils
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        header = np.asarray([len(payload), zlib.crc32(payload),
                             _group_epoch()], np.int64)

        def gather() -> List[Any]:
            headers = np.asarray(multihost_utils.process_allgather(
                header)).reshape(-1, 3)
            lens = headers[:, 0]
            buf = np.zeros(int(lens.max()), np.uint8)
            buf[:len(payload)] = payload
            gathered = _maybe_corrupt(np.asarray(
                multihost_utils.process_allgather(buf)))
            out = []
            for i in range(len(lens)):
                _check_frame_epoch(int(headers[i, 2]), "allgather_object",
                                   peer=i)
                blob = gathered[i, :int(lens[i])]
                crc = zlib.crc32(np.ascontiguousarray(blob))
                # compare in uint32 space: the gloo CPU transport returns
                # int64 headers sign-truncated to 32 bits, so a crc with
                # the top bit set comes back negative while still carrying
                # the full 32 bits of integrity
                want = int(headers[i, 1]) & 0xFFFFFFFF
                if crc != want:
                    raise CollectiveError(
                        f"allgather_object payload from process {i} failed "
                        f"its CRC check (sent {want:08x}, "
                        f"received {crc:08x}) — corrupt or torn transfer")
                out.append(pickle.loads(blob.tobytes()))
            return out

        return _with_timeout(gather, _TIMEOUT, "allgather_object")

    result = _retrying("allgather_object", attempt)
    if len(result) > 1:
        _note("allgather_object", sum(len(pickle.dumps(o)) for o in [obj]))
    return result


def broadcast_object(obj: Any = None) -> Any:
    """Every process receives process 0's object (rank-0 decision sync).

    A real rank-0 length-then-payload broadcast: non-root processes ship
    nothing — they only learn the payload size from the header phase and
    receive the bytes (plus CRC check) in the second."""

    def attempt() -> Any:
        _maybe_inject("broadcast_object")
        _maybe_stale_rejoin("broadcast_object")
        if process_count() == 1:
            return obj
        import jax
        from jax.experimental import multihost_utils
        is_root = jax.process_index() == 0
        payload = (np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
                   if is_root else np.zeros(0, np.uint8))
        header = np.asarray(
            [len(payload), zlib.crc32(payload) if is_root else 0,
             _group_epoch()], np.int64)

        def bcast() -> Any:
            hdr = np.asarray(multihost_utils.broadcast_one_to_all(header))
            _check_frame_epoch(int(hdr[2]), "broadcast_object", peer=0)
            # uint32-space compare: gloo sign-truncates int64 headers
            n, want = int(hdr[0]), int(hdr[1]) & 0xFFFFFFFF
            buf = payload if is_root else np.zeros(n, np.uint8)
            # broadcast_one_to_all's internal psum promotes u8 to u32;
            # restore the byte view or the CRC runs over 4x the bytes
            got = _maybe_corrupt(np.asarray(
                multihost_utils.broadcast_one_to_all(buf), dtype=np.uint8))
            crc = zlib.crc32(np.ascontiguousarray(got[:n]))
            if crc != want:
                raise CollectiveError(
                    f"broadcast_object payload from process 0 failed its "
                    f"CRC check (sent {want:08x}, received {crc:08x}) on "
                    f"process {jax.process_index()}")
            return pickle.loads(got[:n].tobytes())

        out = _with_timeout(bcast, _TIMEOUT, "broadcast_object")
        _note("broadcast_object", int(header[0]))
        return out

    return _retrying("broadcast_object", attempt)
