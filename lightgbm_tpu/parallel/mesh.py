"""Device-mesh utilities.

The reference's distributed substrate is a hand-built TCP/MPI collective layer
(``src/network/``: Bruck allgather, recursive-halving reduce-scatter over a
machine-list file).  On TPU the entire layer collapses to ``jax.sharding.Mesh``
axes + XLA collectives over ICI/DCN: machine-list → mesh construction,
rank → ``lax.axis_index``, Allreduce/ReduceScatter → ``lax.psum`` /
``lax.psum_scatter``.  Multi-host initialization goes through
``jax.distributed.initialize`` (the analogue of ``Network::Init``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
FEATURE_AXIS = "feature"

# GSPMD mesh axes (docs/DISTRIBUTED.md): rows shard over ``batch``, the
# histogram pool over ``feature``.  The shard_map learners keep the
# historical ``data`` spelling above; the named-sharding mesh follows the
# (batch, feature) convention of the block-distributed formulation.
BATCH_AXIS = "batch"


def make_mesh(num_devices: int = 0, axis_name: str = DATA_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the given axis (rows for data-parallel, columns for
    feature-parallel)."""
    devs = list(devices) if devices is not None else jax.devices()
    if num_devices and num_devices > 0:
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def make_2d_mesh(data: int, feature: int) -> Mesh:
    """data x feature mesh for combined row/column sharding — the 2-D
    hybrid learner (``tree_learner=data_feature``,
    parallel/learner.py DataFeatureStrategy)."""
    devs = np.asarray(jax.devices()[:data * feature]).reshape(data, feature)
    return Mesh(devs, (DATA_AXIS, FEATURE_AXIS))


def make_named_mesh(data: int, feature: int,
                    devices: Optional[Sequence] = None) -> Mesh:
    """``(batch, feature)`` named mesh for the GSPMD learners
    (``parallel/gspmd.py``): rows shard over ``batch``, the histogram
    pool over ``feature``.  Either extent may be 1 (pure data- or pure
    feature-sharding); the product must not exceed the device count.

    Spans ALL processes' devices in process-major order: each process's
    local devices occupy a contiguous block of batch-axis rows, so one
    rank's row partition lands exactly on its own devices
    (``jax.make_array_from_process_local_data`` in
    ``boosting._setup_gspmd``) and the elastic shrink path re-cuts the
    same global row order at any world size."""
    devs = list(devices) if devices is not None else jax.devices()
    devs.sort(key=lambda d: (int(getattr(d, "process_index", 0)),
                             int(getattr(d, "id", 0))))
    need = data * feature
    if need > len(devs):
        raise MeshPlanError(
            f"mesh shape {data}x{feature} needs {need} devices; "
            f"{len(devs)} available")
    return Mesh(np.asarray(devs[:need]).reshape(data, feature),
                (BATCH_AXIS, FEATURE_AXIS))


class MeshPlanError(RuntimeError):
    """Structured pre-flight failure of the sharding planner: no mesh
    shape over the available devices fits the predicted per-device peak
    in the capacity budget (the message carries the best candidate's
    component breakdown so the fix — fewer leaves/bins, more chips, a
    bigger budget — is actionable without a debugger)."""


class MeshPlan(NamedTuple):
    """One planner decision (``plan_mesh``): mesh extents, whether the
    binned matrix itself is block-sharded over ``feature`` (vs replicated
    along that axis), and the evidence backing the choice."""
    data: int                  # batch-axis extent
    feature: int               # feature-axis extent
    block_shard_bins: bool     # bins P(batch, feature) vs P(batch, None)
    per_device_bytes: int      # predicted per-device peak at this shape
    capacity: Optional[int]    # budget the plan was judged against
    components: dict           # top per-device components {name: bytes}
    reason: str                # human-readable decision trail


def _mesh_factorizations(n: int):
    """(data, feature) candidates over exactly ``n`` devices, data-major
    first (pure data-parallel is the cheapest shape when it fits: routing
    and split-find stay collective-free)."""
    out = [(d, n // d) for d in range(n, 0, -1) if n % d == 0]
    return out


def mesh_shape_fits_processes(data: int, feature: int, procs: int,
                              local_devices: int) -> Optional[str]:
    """Can a ``(data, feature)`` mesh be laid out so every process's
    local devices tile whole batch-axis rows?  Returns None when it can,
    else the human-readable refusal.  Required for multi-process GSPMD:
    each rank holds its OWN row partition, so its devices must cover a
    contiguous block of batch rows across the FULL feature extent —
    ``data`` a multiple of the process count and the per-process device
    count a multiple of ``feature``."""
    procs = max(1, int(procs))
    if procs == 1:
        return None
    if data % procs != 0:
        return (f"batch extent {data} does not divide over {procs} "
                "processes (each rank's row partition needs whole "
                "batch-axis rows)")
    if local_devices and local_devices % feature != 0:
        return (f"{local_devices} local device(s) per process cannot "
                f"tile {feature} feature shard(s) per batch row")
    return None


def plan_mesh(n_devices: int, rows: int, features: int, bins: int = 255,
              leaves: int = 31, num_class: int = 1,
              bin_bytes: Optional[int] = None, packed_cols: int = 0,
              valid_rows: int = 0, capacity: Optional[int] = None,
              prefer: str = "data", gspmd_fused: bool = False,
              procs: int = 1, local_devices: int = 0) -> MeshPlan:
    """The memory-driven sharding planner (``mesh_shape=auto``).

    Evaluates ``obs/memory.predict_hbm`` per candidate ``(data,
    feature)`` factorization of ``n_devices`` and returns the first shape
    — in preference order — whose predicted per-device peak fits
    ``capacity``.  Preference: pure data-parallel first (``prefer="data"``,
    the shape with no cross-shard routing or split-find traffic), walking
    toward feature-heavy shapes only under memory pressure;
    ``prefer="feature"`` walks the other way (the feature-parallel
    learner's contract), ``prefer="square"`` starts at the most balanced
    factorization (the 2-D hybrid).  Replication is part of the decision:
    a shape is first tried with the binned matrix replicated along
    ``feature`` (cheap routing) and block-sharded over both axes only if
    replication alone does not fit.  With no capacity signal (CPU hosts
    report none) the preferred shape wins outright.

    Multi-process jobs (``procs`` > 1, ``local_devices`` per process):
    candidates that cannot map each process's row partition onto its own
    devices are skipped (:func:`mesh_shape_fits_processes`) — a
    feature-heavy shape a single process could serve may be
    unreachable for a partitioned group, and the planner must say so
    at pre-flight rather than let the array placement fail mid-setup.

    Raises :class:`MeshPlanError` when nothing fits — a structured
    pre-flight error in milliseconds instead of an on-chip OOM minutes
    into a capture window."""
    from ..obs.memory import predict_hbm
    n_devices = max(int(n_devices), 1)
    cands = _mesh_factorizations(n_devices)
    if procs > 1:
        fits = [(d, f) for d, f in cands
                if mesh_shape_fits_processes(d, f, procs,
                                             local_devices) is None]
        if not fits:
            raise MeshPlanError(
                f"no factorization of {n_devices} device(s) lays out over "
                f"{procs} processes x {local_devices or '?'} local "
                "device(s): every candidate leaves some rank's row "
                "partition straddling another process's devices")
        cands = fits
    if prefer == "feature":
        cands = cands[::-1]
    elif prefer == "square":
        cands.sort(key=lambda df: (abs(df[0] - df[1]), -df[0]))

    def per_device(d, f, block):
        p = predict_hbm(rows=rows, features=features, bins=bins,
                        leaves=leaves, num_class=num_class,
                        bin_bytes=bin_bytes, packed_cols=packed_cols,
                        valid_rows=valid_rows, data_shards=d,
                        feature_shards=f, block_shard_bins=block,
                        gspmd_fused=gspmd_fused)
        comps = dict(sorted({**p["residents"], **p["transients"]}.items(),
                            key=lambda kv: -kv[1])[:4])
        return int(p["peak_bytes"]), comps

    best = None            # smallest-peak candidate, for the error message
    for d, f in cands:
        for block in (False, True) if f > 1 else (False,):
            peak, comps = per_device(d, f, block)
            if best is None or peak < best[3]:
                best = (d, f, block, peak, comps)
            if capacity is None or peak <= capacity:
                why = (f"{d}x{f} mesh"
                       + (", bins block-sharded" if block
                          else (", bins replicated over feature"
                                if f > 1 else ""))
                       + (f": predicted per-device peak "
                          f"{peak / 1e9:.2f} GB fits capacity "
                          f"{capacity / 1e9:.2f} GB"
                          if capacity is not None else
                          ": no capacity signal, preferred shape"))
                return MeshPlan(d, f, block, peak, capacity, comps, why)
    d, f, block, peak, comps = best
    detail = ", ".join(f"{k}={v / 1e9:.2f} GB" for k, v in comps.items())
    raise MeshPlanError(
        f"no mesh shape over {n_devices} device(s) fits: best candidate "
        f"{d}x{f}{' (bins block-sharded)' if block else ''} still needs "
        f"{peak / 1e9:.2f} GB per device vs capacity "
        f"{(capacity or 0) / 1e9:.2f} GB (top components: {detail}) — "
        f"shrink the shape (num_leaves/max_bin/rows), add devices, or "
        f"raise hbm_budget")


class PlacementPlan(NamedTuple):
    """One data-placement decision (``resolve_placement``): where the
    binned training matrix lives for this run and the evidence backing
    the choice."""
    mode: str                  # resident | chunked | sharded
    chunk_rows: int            # streamed block size (0 unless chunked)
    mesh: Optional[MeshPlan]   # the mesh plan when mode == "sharded"
    peak_bytes: int            # predicted peak at the chosen placement
    capacity: Optional[int]    # budget the plan was judged against
    components: dict           # top predicted components {name: bytes}
    reason: str                # human-readable decision trail


def default_chunk_rows(rows: int, requested: int = 0) -> int:
    """Streamed block size: the explicit ``stream_chunk_rows`` when
    given (clamped to the row count), else 256k rows capped at
    ``ceil(rows / 2)`` so even a small dataset exercises at least two
    blocks — the double buffer is pointless with one."""
    rows = max(1, int(rows))
    if requested and int(requested) > 0:
        return min(int(requested), rows)
    return max(1, min(262144, -(-rows // 2)))


def resolve_placement(rows: int, features: int, bins: int = 255,
                      leaves: int = 31, num_class: int = 1,
                      bin_bytes: Optional[int] = None,
                      packed_cols: int = 0, valid_rows: int = 0,
                      capacity: Optional[int] = None,
                      data_stream: str = "auto",
                      stream_chunk_rows: int = 0,
                      n_devices: int = 1, prefer: str = "data",
                      gspmd_fused: bool = False, procs: int = 1,
                      local_devices: int = 0) -> PlacementPlan:
    """The unified capacity walk (``data_stream=auto``): decide where the
    binned matrix lives BEFORE anything compiles by evaluating
    ``obs/memory.predict_hbm`` per placement rung —

    1. **resident** — the classic whole-matrix-on-device layout;
    2. **chunked** — streamed out-of-core blocks (data/stream.py): the
       requested (or default) block size first, then halving blocks down
       to a 4096-row floor, since the double-buffer footprint is the
       planner's lever;
    3. **sharded** — hand the shape to :func:`plan_mesh` when more than
       one device is available.

    An explicit ``data_stream=resident|chunked`` pins the rung (the
    budget check still runs later in pre-flight, so a forced placement
    that does not fit fails with the component breakdown rather than an
    on-chip OOM).  Every decision lands as one structured
    ``placement_decision`` obs event; when NOTHING fits the walk raises
    :class:`MeshPlanError` naming the best candidate per rung."""
    from ..obs.counters import counters
    from ..obs.memory import predict_hbm

    def predict(chunk):
        p = predict_hbm(rows=rows, features=features, bins=bins,
                        leaves=leaves, num_class=num_class,
                        bin_bytes=bin_bytes, packed_cols=packed_cols,
                        valid_rows=valid_rows, stream_chunk_rows=chunk)
        comps = dict(sorted({**p["residents"], **p["transients"]}.items(),
                            key=lambda kv: -kv[1])[:4])
        return int(p["peak_bytes"]), comps

    def decide(plan: PlacementPlan) -> PlacementPlan:
        counters.event("placement_decision", mode=plan.mode,
                       chunk_rows=plan.chunk_rows,
                       predicted_peak_bytes=plan.peak_bytes,
                       capacity_bytes=plan.capacity,
                       data_stream=data_stream, reason=plan.reason)
        return plan

    res_peak, res_comps = predict(0)
    if data_stream == "resident":
        return decide(PlacementPlan(
            "resident", 0, None, res_peak, capacity, res_comps,
            "data_stream=resident pinned by config"))
    if data_stream == "auto" and (capacity is None
                                  or res_peak <= capacity):
        why = ("resident: no capacity signal" if capacity is None else
               f"resident: predicted peak {res_peak / 1e9:.2f} GB fits "
               f"capacity {capacity / 1e9:.2f} GB")
        return decide(PlacementPlan("resident", 0, None, res_peak,
                                    capacity, res_comps, why))

    chunk0 = default_chunk_rows(rows, stream_chunk_rows)
    forced_chunk = data_stream == "chunked"
    best_stream = None
    chunk = chunk0
    while True:
        peak, comps = predict(chunk)
        if best_stream is None or peak < best_stream[1]:
            best_stream = (chunk, peak, comps)
        if forced_chunk and stream_chunk_rows:
            # an explicit block size is a pin, not a starting point
            break
        if capacity is not None and peak > capacity and chunk > 4096:
            chunk = max(4096, chunk // 2)
            continue
        break
    chunk, peak, comps = best_stream
    if forced_chunk or capacity is None or peak <= capacity:
        why = (f"chunked: {chunk}-row blocks, predicted peak "
               f"{peak / 1e9:.2f} GB"
               + (" pinned by data_stream=chunked" if forced_chunk else
                  (f" fits capacity {capacity / 1e9:.2f} GB (resident "
                   f"needs {res_peak / 1e9:.2f} GB)"
                   if capacity is not None else "")))
        return decide(PlacementPlan("chunked", chunk, None, peak,
                                    capacity, comps, why))

    if n_devices > 1:
        try:
            mp = plan_mesh(n_devices, rows, features, bins=bins,
                           leaves=leaves, num_class=num_class,
                           bin_bytes=bin_bytes, packed_cols=packed_cols,
                           valid_rows=valid_rows, capacity=capacity,
                           prefer=prefer, gspmd_fused=gspmd_fused,
                           procs=procs, local_devices=local_devices)
        except MeshPlanError:
            mp = None
        if mp is not None:
            return decide(PlacementPlan(
                "sharded", 0, mp, mp.per_device_bytes, capacity,
                mp.components,
                f"sharded: {mp.reason} (resident needs "
                f"{res_peak / 1e9:.2f} GB, best streamed "
                f"{peak / 1e9:.2f} GB)"))

    detail = ", ".join(f"{k}={v / 1e9:.2f} GB" for k, v in comps.items())
    counters.event("placement_decision", mode="refused",
                   chunk_rows=chunk, predicted_peak_bytes=peak,
                   capacity_bytes=capacity, data_stream=data_stream,
                   reason="no placement fits")
    raise MeshPlanError(
        f"no data placement fits capacity "
        f"{(capacity or 0) / 1e9:.2f} GB: resident needs "
        f"{res_peak / 1e9:.2f} GB, best streamed candidate "
        f"({chunk}-row blocks) still needs {peak / 1e9:.2f} GB "
        f"(top components: {detail})"
        + ("" if n_devices > 1 else ", and only 1 device is available "
           "for sharding") +
        " — shrink the shape (num_leaves/max_bin), lower "
        "stream_chunk_rows, add devices, or raise hbm_budget")


def parse_mesh_shape(spec: str, n_devices: int, prefer: str = "data"):
    """``mesh_shape`` parameter -> (data, feature) extents or None for
    ``auto`` (planner decides).  Accepts ``DxF`` (``2x4``), ``data``
    (all devices on the batch axis) and ``feature`` (all on the feature
    axis); rejects shapes the device count cannot serve."""
    s = str(spec or "auto").strip().lower()
    if s in ("", "auto"):
        return None
    if s == "data":
        return (n_devices, 1)
    if s == "feature":
        return (1, n_devices)
    m = s.replace("*", "x").split("x")
    if len(m) == 2 and all(p.strip().isdigit() for p in m):
        d, f = int(m[0]), int(m[1])
        if d < 1 or f < 1:
            raise ValueError(f"mesh_shape extents must be >= 1; got {spec!r}")
        if d * f > n_devices:
            raise ValueError(
                f"mesh_shape {d}x{f} needs {d * f} devices; only "
                f"{n_devices} available")
        return (d, f)
    raise ValueError(
        f"mesh_shape must be 'auto', 'data', 'feature', or 'DxF' "
        f"(e.g. 2x4); got {spec!r}")


# epoch the runtime was last initialized under (the incarnation fence,
# parallel/sync.py): a relaunched in-process training at a NEWER epoch
# tears the stale runtime down and re-initializes instead of rejoining a
# rendezvous its peers already abandoned
_init_epoch: Optional[int] = None


def shutdown_distributed() -> None:
    """Tear the distributed runtime down (idempotent).  The supervisor
    relaunch path spawns fresh processes — their runtimes die with them —
    but an in-process relaunch (tests, embedding hosts) must disconnect
    the dead incarnation's coordination client before the new epoch's
    barrier can form."""
    global _init_epoch
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()
    _init_epoch = None


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout: Optional[float] = None) -> None:
    """Multi-host bring-up (Network::Init analogue; machine-list file →
    coordinator address).  The startup barrier is bounded: a dead peer
    (or a stale survivor holding the old port) surfaces as a catchable
    :class:`~..parallel.sync.CollectiveError` after ``timeout`` seconds
    — with a structured ``distributed_init_failed`` event — never as an
    indefinite hang the supervisor can only SIGKILL."""
    global _init_epoch
    if coordinator_address is None:
        return
    kwargs = {}
    if timeout and timeout > 0:
        kwargs["initialization_timeout"] = max(1, int(timeout))
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **kwargs)
    except RuntimeError as e:
        from ..obs.counters import counters
        from .sync import CollectiveError
        counters.event("distributed_init_failed",
                       coordinator=coordinator_address,
                       num_processes=num_processes, process_id=process_id,
                       timeout=timeout, error=str(e))
        raise CollectiveError(
            f"distributed startup barrier failed for process "
            f"{process_id}/{num_processes} (coordinator "
            f"{coordinator_address}, timeout {timeout}s): {e}") from e
    from ..checkpoint import group_epoch
    _init_epoch = group_epoch()


def parse_machine_list(path: str):
    """Reference mlist format (``Network::Init``, src/network/linkers.cpp):
    one ``ip port`` pair per line."""
    machines = []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) >= 2:
                machines.append((parts[0], int(parts[1])))
    return machines


def write_machine_list(path: str, machines) -> None:
    """Inverse of :func:`parse_machine_list` — the supervisor rewrites the
    list when it refreshes ports between group relaunches."""
    with open(path, "w") as f:
        for ip, port in machines:
            f.write(f"{ip} {port}\n")


def refresh_local_ports(path: str) -> None:
    """Re-point every loopback entry of a machine list at a freshly bound
    (and immediately released) port.  A restarted group reuses its machine
    list, but the dead coordinator's listen port can linger in TIME_WAIT —
    on a single-host group (the CI harness, local supervised runs) fresh
    ports per incarnation make relaunch deterministic.  Non-local entries
    (a real multi-host fleet) are left untouched: their ports are
    infrastructure, not ours to rebind."""
    import socket
    machines = parse_machine_list(path)
    out = []
    for ip, port in machines:
        if ip in ("127.0.0.1", "localhost"):
            s = socket.socket()
            s.bind((ip if ip != "localhost" else "127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
        out.append((ip, port))
    write_machine_list(path, out)


def _local_rank(machines) -> Optional[int]:
    """Find this host in the machine list by its addresses — the reference's
    rank discovery (linkers.cpp matches local interface IPs).  The
    ``LGBM_TPU_RANK`` env var overrides (containers often NAT their IPs)."""
    import os
    import socket
    env = os.environ.get("LGBM_TPU_RANK")
    if env is not None:
        return int(env)
    try:
        local = {"127.0.0.1", "localhost", socket.gethostname(),
                 socket.gethostbyname(socket.gethostname())}
    except OSError:
        local = {"127.0.0.1", "localhost"}
    matches = [i for i, (ip, _) in enumerate(machines) if ip in local]
    if len(matches) > 1:
        # several workers on one host (duplicate IPs in the list): address
        # matching cannot disambiguate — the caller must set LGBM_TPU_RANK
        return None
    return matches[0] if matches else None


def init_distributed_from_config(cfg) -> bool:
    """Wire ``machine_list_file`` / ``num_machines`` into
    ``jax.distributed.initialize`` — the analogue of the reference CLI's
    network bring-up (``src/application/application.cpp:190-224``).

    Machine 0 is the coordinator; its listed port doubles as the JAX
    coordination-service port.  Rank comes from ``LGBM_TPU_RANK`` or from
    matching local addresses against the list.  Returns True when running
    multi-process (freshly initialized or already up).

    Epoch fence at the startup barrier: when a supervisor stamped the
    group's current incarnation into the epoch file
    (``checkpoint.group_epoch_path``), a worker launched under an OLDER
    epoch raises :class:`~.sync.StaleEpochError` before touching the
    rendezvous — the startup-barrier extension of the per-payload fence
    in parallel/sync.py.  A runtime initialized under a PREVIOUS epoch
    (in-process relaunch) is torn down and re-initialized rather than
    rejoined."""
    from ..utils import log
    from ..checkpoint import group_epoch, read_group_epoch_file
    if getattr(cfg, "num_machines", 1) <= 1:
        return False
    my_epoch = group_epoch()
    stamped = read_group_epoch_file(getattr(cfg, "output_model", "") or "")
    if stamped is not None and stamped > my_epoch:
        from ..obs.counters import counters
        from .sync import StaleEpochError
        counters.event("stale_epoch_rejected", op="distributed_init",
                       frame_epoch=my_epoch, group_epoch=stamped)
        raise StaleEpochError(
            f"startup barrier refused: this process was launched under "
            f"epoch {my_epoch} but the group is at epoch {stamped} — a "
            f"stale incarnation must not join the new rendezvous",
            frame_epoch=my_epoch, group_epoch=stamped)
    # must not touch the backend (jax.devices/process_count) before
    # jax.distributed.initialize; use is_initialized to test idempotently
    if jax.distributed.is_initialized():
        if _init_epoch is not None and _init_epoch != my_epoch:
            # in-process relaunch under a new incarnation: the old
            # runtime's coordination client belongs to a dead group
            log.info("Distributed runtime is from epoch %s; re-initializing "
                     "under epoch %d", _init_epoch, my_epoch)
            shutdown_distributed()
        else:
            return True                  # already initialized, same epoch
    if not cfg.machine_list_file:
        log.fatal("num_machines=%d but no machine_list_file given",
                  cfg.num_machines)
    machines = parse_machine_list(cfg.machine_list_file)[:cfg.num_machines]
    if len(machines) < cfg.num_machines:
        log.fatal("machine_list_file lists %d machines, num_machines=%d",
                  len(machines), cfg.num_machines)
    rank = _local_rank(machines)
    if rank is None:
        log.fatal("cannot determine this machine's rank: no local address in "
                  "%s (set LGBM_TPU_RANK)", cfg.machine_list_file)
    coordinator = f"{machines[0][0]}:{machines[0][1]}"
    log.info("Initializing distributed runtime: %d machines, rank %d, "
             "coordinator %s", len(machines), rank, coordinator)
    init_distributed(coordinator, len(machines), rank,
                     timeout=getattr(cfg, "collective_timeout", 0.0))
    return True


def pad_rows(n: int, shards: int) -> int:
    """Rows padded so every shard gets an equal static slice."""
    return (-n) % shards


def pad_features(f: int, shards: int) -> int:
    return (-f) % shards
