"""Device-memory observability: live HBM accounting, compiled-executable
memory analysis, and the measured fit-predictor.

On TPU the hard wall is HBM, not FLOPs — ``docs/MEMORY.md``'s Epsilon-like
``hist_store`` alone is 1.56 GB — and before this module that table was
hand-computed: nothing ever measured actual device bytes, so a wrong
estimate surfaced as an opaque on-chip OOM during a scarce capture window.
Three legs, each independently usable:

* **live accounting** — :class:`MemoryMonitor`, armed through
  :func:`start`/:func:`stop` with the established no-op-singleton
  discipline (``obs/trace.py``, ``utils/faults.py``): when disarmed the
  active monitor is the shared :data:`NULL_MEMORY` whose every method is a
  constant no-op, so the instrumented hot paths (per-iteration sample,
  per-phase span annotation) cost one attribute read.  Armed, each sample
  reads ``device.memory_stats()`` where the backend provides it (TPU) and
  falls back to a ``jax.live_arrays()`` census elsewhere (CPU) — both are
  host-side reads, so sampling adds ZERO host<->device synchronizations
  (the rule PR 3's non-finite guards established).  Census bytes are
  attributed to owner tags (binned matrix, scores, bagging, ...) through
  resident providers the boosting driver registers
  (:func:`register_residents`).

* **static analysis** — :func:`executable_memory` wraps
  ``compiled.memory_analysis()`` (argument/output/temp/alias bytes of a
  jitted executable) into a plain dict, records the numbers as obs
  gauges + one ``exec_memory`` event, and is what
  ``scripts/profile_grow_steps.py`` and the ``tests/test_grow_jaxpr.py``
  byte-budget ratchet consume: a copy-insertion regression now fails a
  CPU test instead of an on-chip capture window.

* **fit prediction** — :func:`predict_hbm` codifies the
  ``docs/MEMORY.md`` analytic model (regenerated from this function by
  ``scripts/gen_memory_doc.py``); :func:`preflight` compares the
  predicted peak against the device capacity (or an explicit
  ``hbm_budget`` param) BEFORE the grower compiles, turning on-chip OOMs
  into actionable pre-flight diagnostics.  Predicted-vs-measured
  agreement is validated on CPU in tier-1 (``tests/test_memory.py``)
  within the documented tolerance (see :data:`RESIDENT_TOLERANCE`).
"""
from __future__ import annotations

import json
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

from .counters import counters

# Documented predicted-vs-measured tolerance for the RESIDENT bytes model
# on the CPU live-array census (tests/test_memory.py, bench.py memory
# block): the census counts real allocator bytes while the model counts
# ideal array payloads, so padding/rounding plus small untracked arrays
# (tree SoA, feature meta, pipeline pending records) make the ratio drift
# from 1.  The acceptance band is measured/predicted in
# [1 - RESIDENT_TOLERANCE, 1 + RESIDENT_TOLERANCE].
RESIDENT_TOLERANCE = 0.35


# --------------------------------------------------------------- live stats


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Normalized ``device.memory_stats()`` or None when the backend does
    not expose allocator stats (CPU).  Keys (when present):
    ``bytes_in_use``, ``peak_bytes_in_use``, ``bytes_limit``."""
    import jax
    # local_devices: under multi-process jax.devices()[0] belongs to
    # rank 0, and another process's device has no stats to read
    dev = device if device is not None else jax.local_devices()[0]
    stats = dev.memory_stats()
    if not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "peak_bytes_reserved", "largest_free_block_bytes",
                "num_allocs"):
        if key in stats:
            out[key] = int(stats[key])
    return out or None


def gauge_hbm_peaks(device=None) -> None:
    """Gauges ``hbm_in_use_peak_bytes`` / ``hbm_reserved_peak_bytes``: what
    the allocator held and what the runtime reserved for compiled
    programs' temporaries, to be read beside ``hbm_predicted_peak_bytes``
    (the pre-flight's ``predict_hbm``).  Nothing off the chip."""
    stats = device_memory_stats(device)
    if stats:
        counters.gauge("hbm_in_use_peak_bytes",
                       stats.get("peak_bytes_in_use", 0))
        counters.gauge("hbm_reserved_peak_bytes",
                       stats.get("peak_bytes_reserved", 0))


# Owner-tag providers: each is a (weakly referenced) zero-arg callable
# returning {tag: [jax arrays]}.  The boosting driver registers its bound
# method here at setup; dead boosters drop out automatically.
_providers: List[Any] = []


def register_residents(provider: Callable[[], Dict[str, list]]) -> None:
    """Register an owner-tag provider for the live-array census.  Bound
    methods are held through ``weakref.WeakMethod`` so a provider never
    keeps its booster alive."""
    try:
        ref = weakref.WeakMethod(provider)
    except TypeError:
        ref = weakref.ref(provider)
    _providers.append(ref)


def live_census() -> Dict[str, Any]:
    """One pass over ``jax.live_arrays()``: total bytes plus a per-owner-tag
    breakdown.  Arrays no registered provider claims land in ``untagged``
    (jit-internal temporaries never appear here at all — XLA workspace is
    not a jax array; on TPU it is covered by ``memory_stats`` instead)."""
    import jax
    tag_of: Dict[int, str] = {}
    live_refs = []
    for ref in _providers:
        fn = ref()
        if fn is None:
            continue
        live_refs.append(ref)
        try:
            owned = fn()
        except Exception:
            continue
        for tag, arrays in owned.items():
            for a in arrays:
                if a is not None:
                    tag_of[id(a)] = tag
    _providers[:] = live_refs
    by_tag: Dict[str, int] = {}
    total = 0
    for a in jax.live_arrays():
        try:
            nbytes = int(a.nbytes)
        except Exception:
            continue
        total += nbytes
        tag = tag_of.get(id(a), "untagged")
        by_tag[tag] = by_tag.get(tag, 0) + nbytes
    return {"total_bytes": total, "by_tag": by_tag}


class NullMemoryMonitor:
    """Disarmed monitor: every operation is a constant no-op, shared
    process-wide (the tracer/faults singleton discipline) so the hot-loop
    sample/annotate sites never allocate when memory observability is
    off."""
    enabled = False
    source = None

    def sample(self, site: str = "") -> Optional[int]:
        return None

    def annotate(self, span) -> None:
        pass

    def measured_peak(self) -> int:
        return 0

    def baseline(self) -> int:
        return 0

    def top_residents(self, k: int = 6) -> List[Dict[str, Any]]:
        return []

    def summary(self) -> Dict[str, Any]:
        return {}


NULL_MEMORY = NullMemoryMonitor()


class MemoryMonitor:
    """Armed monitor.  ``source`` names the evidence backing the numbers:
    ``memory_stats`` (TPU allocator truth, includes XLA workspace) or
    ``live_census`` (CPU fallback: persistent jax arrays only)."""
    enabled = True

    def __init__(self):
        self._peak = 0
        self._flight_mark = 0
        self._last_census: Optional[Dict[str, Any]] = None
        stats = device_memory_stats()
        self.source = "memory_stats" if stats else "live_census"
        self._baseline = (stats["bytes_in_use"] if stats
                          and "bytes_in_use" in stats
                          else live_census()["total_bytes"])
        counters.gauge("memory_baseline_bytes", self._baseline)

    def sample(self, site: str = "") -> Optional[int]:
        """Record the current device occupancy; returns the sampled bytes.
        Host-side reads only — never synchronizes the device."""
        stats = device_memory_stats() if self.source == "memory_stats" \
            else None
        if stats:
            in_use = stats.get("bytes_in_use", 0)
            peak = stats.get("peak_bytes_in_use", in_use)
        else:
            self._last_census = live_census()
            in_use = peak = self._last_census["total_bytes"]
        self._peak = max(self._peak, peak)
        counters.gauge("memory_bytes_in_use", in_use)
        counters.gauge("memory_peak_bytes", self._peak)
        if self._peak > self._flight_mark * 1.1:
            # flight-recorder inflection: the peak grew >10% past its last
            # streamed mark — a live stream shows WHEN memory jumped, not
            # just the final number (no-op singleton when disarmed)
            self._flight_mark = self._peak
            from .flight import get_flight
            get_flight().record("hbm_peak", peak_bytes=int(self._peak),
                                site=site, source=self.source)
        return in_use

    def annotate(self, span) -> None:
        """Attach the sampled bytes to a recording tracer span (the
        ``PhaseTimers`` hook).  A ``NULL_SPAN`` has no ``_args`` and is
        skipped, so the disabled-tracer fast path stays allocation-free."""
        args = getattr(span, "_args", None)
        if args is None:
            return
        b = self.sample(site="phase")
        if b is not None:
            args["peak_bytes"] = int(self._peak)

    def measured_peak(self) -> int:
        return self._peak

    def baseline(self) -> int:
        return self._baseline

    def top_residents(self, k: int = 6) -> List[Dict[str, Any]]:
        """Largest owner tags of the most recent census (taken on demand
        when the monitor rides ``memory_stats`` — the tag breakdown is a
        census-only view either way)."""
        census = self._last_census or live_census()
        tags = sorted(census["by_tag"].items(), key=lambda kv: -kv[1])
        return [{"tag": t, "bytes": b} for t, b in tags[:k]]

    def summary(self) -> Dict[str, Any]:
        return {"source": self.source,
                "baseline_bytes": self._baseline,
                "measured_peak_bytes": self._peak,
                "top_residents": self.top_residents()}


_active: Any = NULL_MEMORY


def get_memory():
    """The process-wide active monitor (NULL_MEMORY when disarmed)."""
    return _active


def start() -> MemoryMonitor:
    """Arm a recording monitor as the process-wide active one."""
    global _active
    _active = MemoryMonitor()
    return _active


def stop() -> Dict[str, Any]:
    """Disarm; flushes the final summary into the counter registry (one
    ``memory_summary`` event + gauges) so a trace written afterwards is
    self-contained, and returns it."""
    global _active
    mon, _active = _active, NULL_MEMORY
    if not mon.enabled:
        return {}
    mon.sample(site="final")
    summ = mon.summary()
    counters.gauge("memory_measured_peak_bytes", summ["measured_peak_bytes"])
    counters.event("memory_summary", **{
        k: v for k, v in summ.items() if k != "top_residents"},
        top_residents=[f"{r['tag']}={r['bytes']}"
                       for r in summ["top_residents"]])
    return summ


# ---------------------------------------------------------- static analysis


def executable_memory(compiled, label: str = "") -> Optional[Dict[str, int]]:
    """``compiled.memory_analysis()`` as a plain dict (bytes):
    ``argument/output/temp/alias/generated_code`` plus ``peak_bytes``
    (argument + output + temp — the executable's device footprint while it
    runs).  With ``label`` the numbers also land as obs gauges
    (``exec_<label>_{temp,peak}_bytes``) and one ``exec_memory`` event.
    Returns None when the backend reports nothing."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         + out["temp_bytes"])
    if label:
        counters.gauge(f"exec_{label}_temp_bytes", out["temp_bytes"])
        counters.gauge(f"exec_{label}_peak_bytes", out["peak_bytes"])
        counters.event("exec_memory", label=label, **out)
    return out


def analyze_jitted(fn, *args, label: str = "") -> Optional[Dict[str, int]]:
    """AOT lower+compile ``fn`` at ``args`` (arrays or ShapeDtypeStructs)
    and return :func:`executable_memory` of the result.  This compiles —
    use it from profilers/tests, never from a training hot path (the
    persistent compilation cache makes repeats cheap)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return executable_memory(compiled, label=label)


# ------------------------------------------------------------ fit predictor


def predict_hbm(rows: int, features: int, bins: int = 255, leaves: int = 31,
                num_class: int = 1, bin_bytes: Optional[int] = None,
                packed_cols: int = 0, valid_rows: int = 0,
                bucket_min_log2: int = 6, serving_trees: int = 0,
                serving_nodes: int = 0, serving_cols: int = 0,
                serving_bins: int = 0,
                serving_buckets: Sequence[int] = (),
                data_shards: int = 1, feature_shards: int = 1,
                block_shard_bins: bool = False,
                gspmd_fused: bool = False,
                stream_chunk_rows: int = 0) -> Dict[str, Any]:
    """Analytic device-memory model of one training (the codified
    ``docs/MEMORY.md`` audit; that doc's table is generated from this
    function by ``scripts/gen_memory_doc.py``).

    ``features`` counts PHYSICAL binned columns (post-EFB).  Components
    split into **residents** — persistent jax arrays the boosting driver
    holds between iterations, what the CPU live-array census sees — and
    **transients** — XLA workspace of the jitted grower (gather staging,
    ``order``, ``hist_store``), visible only to ``memory_stats`` on TPU.
    ``peak_bytes`` = residents + transients; ``resident_bytes`` is the
    number the census-based CPU validation compares against (tolerance
    :data:`RESIDENT_TOLERANCE`).

    ``data_shards``/``feature_shards`` turn the model PER-DEVICE for a
    GSPMD ``(batch, feature)`` mesh (docs/DISTRIBUTED.md): row-linear
    terms divide by ``data_shards``, the histogram pool by
    ``feature_shards``, and the binned matrix additionally by
    ``feature_shards`` when ``block_shard_bins`` (``shard_axes``
    block-shards the data itself).  This is what makes the function the
    sharding PLANNER's cost model (``parallel/mesh.plan_mesh``): the
    planner evaluates it per candidate mesh shape and picks one whose
    per-device peak fits the chip.  Defaults (1, 1) reproduce the
    single-device model unchanged.

    ``stream_chunk_rows`` > 0 models the STREAMED single-device mode
    (``data_stream=chunked``; data/stream.py): the binned matrix stays
    host-side, so its resident term vanishes and is replaced by the
    double-buffered pair of static-shape row blocks, the per-block
    row->leaf routing vectors, and the carried histogram pool — the terms
    that make HBM a function of the CHUNK size instead of the row count.
    """
    rows = int(rows)
    features = int(features)
    d = max(int(data_shards), 1)
    fs = max(int(feature_shards), 1)
    rows_d = -(-rows // d)                  # rows per data shard (ceil)
    if bin_bytes is None:           # the binned matrix's dtype (dataset.py)
        bin_bytes = 1 if bins <= 256 else 2
    # the largest window of the grower's own table (lazy: grower imports obs)
    from ..grower import (GrowerConfig, _bucket_sizes, _order_tail,
                          _partition_sizes)
    gcfg = GrowerConfig(bucket_min_log2=bucket_min_log2)
    maxbuf = _bucket_sizes(gcfg, rows_d)[-1]
    # the partition's own, shorter table sets ``order``'s sentinel tail
    order_tail = _order_tail(_partition_sizes(gcfg, rows_d))
    residents = {
        # the binned matrix [N, C] (+ the nibble-packed histogram copy):
        # row-sharded over ``batch``; over ``feature`` too when the
        # planner block-shards it
        "binned": rows_d * -(-features // (fs if block_shard_bins else 1))
        * bin_bytes,
        "packed": rows_d * int(packed_cols),
        # train scores live twice per class: the current array + the
        # iteration-start rollback stash (boosting.train_one_iter)
        "scores": 2 * num_class * rows_d * 4,
        # per-iteration gradient/hessian pair, alive through the tree phase
        "grad_hess": 2 * num_class * rows_d * 4,
        # the objective's label + ~2 derived per-row device vectors
        # (binary's sign/weight; a rough but measured-against constant)
        "objective": 3 * rows_d * 4,
        # bagging weight + count vectors
        "bagging": 2 * rows_d * 4,
        # each valid set: binned matrix + per-class scores
        "valid": -(-int(valid_rows) // d) * (features * bin_bytes
                                             + num_class * 4),
    }
    # a row of the histogram inputs as the grower stages and gathers it:
    # the [W + 3] u32 word panel for bins of at most 2 bytes, bins + g, h, c
    # apart for wider ones
    row_bytes = ((-(-features * bin_bytes // 4) + 3) * 4 if bin_bytes <= 2
                 else features * bin_bytes + 12)
    # the per-leaf histogram pool [L, F, B, 3] f32 — sharded over the
    # ``feature`` mesh axis (the planner's main lever: this is the
    # component that outgrows a chip first at Epsilon-wide shapes)
    pool_bytes = leaves * -(-features // fs) * bins * 3 * 4

    def panel_width(cols):
        """u32 words a row of the fused kernel's packed panel, padded to
        the 128 lanes: the bins of ``cols`` columns 4 (1-byte) or 2 to a
        word, and g, h, c."""
        per = 4 if bin_bytes == 1 else 2
        words = -(-(-(-cols // 8) * 8) // per) + 3
        return -(-words // 128) * 128

    # gspmd_hist=fused on a mesh of row shards alone runs the serial grower
    # on each device's rows (parallel/gspmd.py): the serial layout per shard
    shard_local = gspmd_fused and fs == 1 and not block_shard_bins
    if (d > 1 or fs > 1) and not shard_local:
        # GSPMD grower layout (parallel/gspmd.py): no gather buckets, no
        # sentinel staging, no ``order`` permutation — the partition is
        # the row_leaf map and the per-split histogram is one flat
        # masked scatter-add whose workspace (segment indices i32 + the
        # broadcast (g, h, c) value rows) covers this device's row shard
        # x its histogram columns (all columns when the binned matrix is
        # replicated along ``feature``, its own slice when block-sharded)
        if gspmd_fused:
            # hybrid grower (gspmd_hist=fused): each device packs its
            # (row shard x feature slice) of the binned matrix into the
            # gather-word panel once per grow and runs the fused Mosaic
            # kernel per split — the scatter workspace is replaced by
            # the resident-sized panel plus the compacted order vector
            # (with its aligned over-fetch tail)
            width = panel_width(-(-(int(packed_cols) or features) // fs))
            transients = {
                "fused_panel": (rows_d + 1) * width * 4,
                "fused_order": (rows_d + 2048) * 4,
                # row_leaf carry + routing column + child mask
                "row_leaf": 3 * rows_d * 4,
                "hist_store": pool_bytes,
            }
        else:
            fcols = -(-features // (fs if block_shard_bins else 1))
            transients = {
                "hist_scatter": rows_d * fcols * 16,
                # row_leaf carry + routing column + child mask
                "row_leaf": 3 * rows_d * 4,
                "hist_store": pool_bytes,
            }
    elif stream_chunk_rows and int(stream_chunk_rows) > 0:
        # streamed out-of-core mode (data_stream=chunked; data/stream.py
        # + grower.StreamedGrower): the binned matrix never becomes
        # device-resident — the device holds the DOUBLE-BUFFERED pair of
        # static-shape row blocks, the per-block row->leaf routing
        # vectors (alive across the whole tree), and the carried
        # histogram pool; the per-split workspace is the masked
        # scatter-add over ONE block (segment indices i32 + broadcast
        # (g, h, c) value rows), so it scales with the chunk, not N
        chunk = min(int(stream_chunk_rows), rows_d)
        residents["binned"] = 0
        residents["stream_blocks"] = 2 * chunk * features * bin_bytes
        residents["stream_row_leaf"] = rows_d * 4
        residents["hist_pool"] = pool_bytes
        transients = {
            "stream_hist_scatter": chunk * features * 16,
        }
    else:
        transients = {
            # sentinel-padded copy of the histogram inputs
            "staging": (rows_d + 1) * row_bytes,
            # order [N + tail] i32, the dense row->leaf vector the
            # partition carries and the final row->leaf map, [N] i32 each
            "order_partition": (rows_d + order_tail) * 4 + 2 * rows_d * 4,
            # the dense partition branch: its sort's key and result
            "partition_dense_sort": 2 * rows_d * 4,
            "hist_store": pool_bytes,
            # the gather buffer for the largest window
            "gather_buffer": maxbuf * row_bytes,
        }
        if shard_local:
            # the fused kernel reads two lane-padded u32 panels of the
            # shard's rows (the sentinel-padded copy and the packed panel:
            # 1,024 B a row at 28 one-byte columns, 10.75 GB of a chip's
            # 11.4 at 10.5M rows in the v5e compile, PERF.md PR 38) where
            # the line above counts the inputs' own bytes; it gathers
            # nothing
            transients["staging"] = 2 * (rows_d + 1) * panel_width(
                int(packed_cols) or features) * 4
            del transients["gather_buffer"]
    if serving_trees > 0:
        # the serving engine's term (docs/SERVING.md): resident SoA node
        # arrays [Tp, P] (feat/thr/left/right i32 + miss/cat_ref i32 +
        # default_left/is_cat bool = 26 B/node) + the per-column bin
        # threshold tables; transient per-bucket microbatch buffers (raw
        # f32 input + bins/cats i32 + nan/zero masks + per-tree
        # node/leaf/output state), summed over the ladder — pessimistic
        # by design, a pre-flight bound, since at most one bucket is in
        # flight per engine at a time
        residents["serving_model"] = (serving_trees * serving_nodes * 26
                                      + serving_cols * serving_bins * 4)
        transients["serving_batches"] = sum(
            b * (serving_cols * 14 + serving_trees * 12)
            for b in serving_buckets)
    resident_bytes = sum(residents.values())
    transient_bytes = sum(transients.values())
    return {
        "inputs": {"rows": rows, "features": features, "bins": bins,
                   "leaves": leaves, "num_class": num_class,
                   "bin_bytes": bin_bytes, "packed_cols": int(packed_cols),
                   "valid_rows": int(valid_rows),
                   "data_shards": d, "feature_shards": fs,
                   "block_shard_bins": bool(block_shard_bins),
                   "gspmd_fused": bool(gspmd_fused),
                   "stream_chunk_rows": int(stream_chunk_rows)},
        "residents": residents,
        "transients": transients,
        "resident_bytes": resident_bytes,
        "transient_bytes": transient_bytes,
        "peak_bytes": resident_bytes + transient_bytes,
    }


def device_capacity(device=None) -> Optional[int]:
    """Total device memory in bytes (``bytes_limit``).  None only where
    the backend keeps no allocator stats (CPU: host memory is not the
    budgeted resource); a TPU that reports no limit is an error, not a
    placement walk that quietly goes resident."""
    import jax
    dev = device if device is not None else jax.local_devices()[0]
    stats = device_memory_stats(dev)
    if stats and "bytes_limit" in stats:
        return stats["bytes_limit"]
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no bytes_limit (memory_stats={stats}); the "
            "placement pre-flight needs the device capacity")
    return None


def preflight(pred: Dict[str, Any], hbm_budget: float = 0.0,
              context: str = "") -> Dict[str, Any]:
    """Compare a :func:`predict_hbm` prediction against the device budget
    BEFORE anything compiles.

    ``hbm_budget`` > 0 is a hard budget in bytes: exceeding it raises
    (``log.fatal``) with the component breakdown — the whole point is to
    fail in seconds on host instead of minutes into a capture window.
    With no explicit budget the check is advisory: when the backend
    reports a capacity (TPU) and the predicted peak exceeds it, a warning
    names the dominant components.  Every outcome lands as one
    ``hbm_preflight`` obs event + a ``hbm_predicted_peak_bytes`` gauge."""
    from ..utils import log
    peak = int(pred["peak_bytes"])
    capacity = device_capacity()
    budget = int(hbm_budget) if hbm_budget and hbm_budget > 0 else None
    limit = budget if budget is not None else capacity
    top = sorted({**pred["residents"], **pred["transients"]}.items(),
                 key=lambda kv: -kv[1])[:3]
    detail = ", ".join(f"{k}={v / 1e9:.2f} GB" for k, v in top)
    counters.gauge("hbm_predicted_peak_bytes", peak)
    verdict = "ok"
    if limit is not None and peak > limit:
        verdict = "over_budget" if budget is not None else "over_capacity"
    counters.event("hbm_preflight", predicted_peak_bytes=peak,
                   capacity_bytes=capacity, hbm_budget=budget,
                   verdict=verdict, context=context)
    if verdict == "over_budget":
        log.fatal("predicted peak HBM %.2f GB exceeds hbm_budget %.2f GB "
                  "(%s; top components: %s) — shrink the shape "
                  "(max_bin/num_leaves/rows) or raise hbm_budget",
                  peak / 1e9, limit / 1e9, context or "pre-flight", detail)
    if verdict == "over_capacity":
        log.warning("predicted peak HBM %.2f GB exceeds device capacity "
                    "%.2f GB (%s; top components: %s) — an on-chip OOM is "
                    "likely; set hbm_budget to fail fast",
                    peak / 1e9, limit / 1e9, context or "pre-flight",
                    detail)
    return {"predicted_peak_bytes": peak, "capacity_bytes": capacity,
            "hbm_budget": budget, "verdict": verdict}


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m lightgbm_tpu.obs.memory`` — one JSON snapshot of every
    device's ``memory_stats`` plus the live-array census; the capture
    playbook collects one per bench rung."""
    import jax
    snap = {"devices": [{"id": int(d.id), "platform": d.platform,
                         "memory_stats": device_memory_stats(d)}
                        for d in jax.devices()],
            "live_census": live_census()}
    print(json.dumps(snap, indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
