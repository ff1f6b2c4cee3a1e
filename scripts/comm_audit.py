"""Collective-cost audit for the distributed tree learners.

Measures (not estimates) the collective traffic each learner issues, by
intercepting ``lax.psum`` / ``lax.pmax`` / ``lax.pmin`` / ``lax.all_gather``
while the distributed grower is being traced over the virtual 8-device CPU
mesh.  The grow loop is a single ``lax.while_loop`` whose body is traced
exactly once, so every collective recorded from inside ``body`` is the
PER-SPLIT set and everything else is the per-tree setup set — the same
separation the reference draws between its per-split ReduceScatter
(data_parallel_tree_learner.cpp:148-163) and its per-tree global stats.

The interception itself is ``lightgbm_tpu.obs.collectives.intercept`` (the
telemetry subsystem's shared helper — record fields are unchanged from the
private ``_record``/``_nbytes`` this script used to carry).

Writes a JSON table to stdout; docs/PARALLEL_COST.md is generated from it
(scripts/comm_audit.py --markdown > docs/PARALLEL_COST.md).

No chip is needed: collective SHAPES are backend-independent (the mesh is
the unit of sharding, not the wire), so the byte counts hold for any
8-shard TPU slice; the time estimates use published v5e ICI numbers and
are labeled as estimates.
"""
import argparse
import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

jax.config.update("jax_platforms", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.utils.cache import enable_persistent_cache  # noqa: E402
enable_persistent_cache()

from lightgbm_tpu.grower import FeatureMeta, GrowerConfig  # noqa: E402
# the interception machinery (lax monkeypatch, byte counting, the
# per-split/per-tree stack classifier) lives in the telemetry subsystem
# now; this script only drives it and formats the tables
from lightgbm_tpu.obs import collectives as obs_coll  # noqa: E402
from lightgbm_tpu.parallel.learner import (  # noqa: E402
    make_distributed_grower)
from lightgbm_tpu.parallel.mesh import make_2d_mesh  # noqa: E402


def audit(learner, n_feat, max_bin, num_leaves=255, top_k=20):
    """Trace the distributed grower once and bucket its collectives."""
    n_rows = 8 * 1024          # shape-irrelevant for collective payloads
    cfg = GrowerConfig(num_leaves=num_leaves, max_bin=max_bin,
                       min_data_in_leaf=1, hist_method="segment")
    if learner == "data_feature":
        mesh = make_2d_mesh(4, 2)
    else:
        devs = jax.devices()[:8]
        import numpy as np
        axis = "feature" if learner == "feature" else "data"
        mesh = Mesh(np.array(devs), (axis,))
    f_pad = -(-n_feat // 8) * 8      # feature learner: multiple of shards
    with obs_coll.intercept() as records:
        fn = make_distributed_grower(cfg, mesh, learner, top_k=top_k)
        bins = jax.ShapeDtypeStruct((n_rows, f_pad), jnp.uint8)
        w = jax.ShapeDtypeStruct((n_rows,), jnp.float32)
        meta = FeatureMeta(
            num_bin=jax.ShapeDtypeStruct((f_pad,), jnp.int32),
            missing_type=jax.ShapeDtypeStruct((f_pad,), jnp.int32),
            default_bin=jax.ShapeDtypeStruct((f_pad,), jnp.int32),
            is_categorical=jax.ShapeDtypeStruct((f_pad,), jnp.bool_))
        fv = jax.ShapeDtypeStruct((f_pad,), jnp.bool_)
        fn.lower(bins, w, w, w, meta, fv)
    per_split = [r for r in records if r["per_split"]]
    per_tree = [r for r in records if not r["per_split"]]
    # the per-split classifier matches a stack frame literally named
    # 'body' inside grower.py; data/voting MUST issue per-split psums, so
    # an empty set means the grower's while-loop body function was
    # renamed and every collective silently reclassified as per-tree
    # setup — fail loudly instead of generating a wrong PARALLEL_COST.md
    if learner in ("data", "voting") and not per_split:
        raise AssertionError(
            f"{learner} learner traced 0 per-split collectives: the "
            "'body' stack-frame classifier in obs.collectives."
            "classify_site() no longer matches grower.py's while-loop "
            "body function")
    return {
        "learner": learner, "features": n_feat, "max_bin": max_bin,
        "num_leaves": num_leaves,
        "per_split_ops": len(per_split),
        "per_split_bytes": sum(r["bytes"] for r in per_split),
        "per_split_detail": per_split,
        "setup_ops": len(per_tree),
        "setup_bytes": sum(r["bytes"] for r in per_tree),
        "per_tree_bytes": (sum(r["bytes"] for r in per_split)
                           * (num_leaves - 1)
                           + sum(r["bytes"] for r in per_tree)),
    }


# v5e: 4 ICI links/chip, 45 GB/s each direction per link (published);
# a ring all-reduce moves 2*(S-1)/S * payload over the slowest link.
ICI_GBPS = 45.0


def ring_ms(payload_bytes, shards=8):
    return payload_bytes * 2 * (shards - 1) / shards / (ICI_GBPS * 1e9) * 1e3


SHAPES = [("higgs", 28, 255), ("wide", 2000, 255), ("wide63", 2000, 63)]
LEARNERS = ["data", "voting", "feature", "data_feature"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = []
    for name, f, b in SHAPES:
        for ln in LEARNERS:
            r = audit(ln, f, b)
            r["shape"] = name
            r["est_ici_ms_per_split"] = round(ring_ms(r["per_split_bytes"]),
                                              4)
            r["est_ici_ms_per_tree"] = round(ring_ms(r["per_tree_bytes"]), 2)
            rows.append(r)
            print(f"# {name} {ln}: {r['per_split_ops']} ops, "
                  f"{r['per_split_bytes']/1e6:.3f} MB/split, "
                  f"{r['per_tree_bytes']/1e6:.1f} MB/tree, "
                  f"~{r['est_ici_ms_per_tree']:.2f} ms/tree ICI",
                  file=sys.stderr)
    if args.markdown:
        print(_markdown(rows))
    else:
        print(json.dumps(rows, indent=1))


def _markdown(rows):
    out = ["# Multi-chip collective cost audit (measured at trace time)",
           "",
           "Generated by `python scripts/comm_audit.py --markdown`; "
           "collective payloads are read off the traced grow program on "
           "the 8-virtual-device CPU mesh (shapes are backend-independent; "
           "time estimates use v5e ICI at 45 GB/s/link, ring all-reduce "
           "2(S-1)/S, and are estimates; the last section gives the data "
           "learner's collectives as compiled for four v5e chips, PR 38).",
           "",
           "| shape | learner | per-split colls | MB/split | MB/tree | "
           "est. ICI ms/tree |",
           "|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['shape']} F={r['features']} B={r['max_bin']} "
            f"| {r['learner']} | {r['per_split_ops']} "
            f"| {r['per_split_bytes']/1e6:.3f} "
            f"| {r['per_tree_bytes']/1e6:.1f} "
            f"| {r['est_ici_ms_per_tree']:.2f} |")
    out.append("")
    out.append("## Per-split collective sites (largest shape per learner)")
    out.append("")
    seen = set()
    for r in rows:
        if r["learner"] in seen or r["shape"] != "wide":
            continue
        seen.add(r["learner"])
        out.append(f"### {r['learner']} (wide, F=2000, B=255)")
        out.append("")
        for d in r["per_split_detail"]:
            out.append(f"- `{d['op']}` {d['bytes']/1e6:.3f} MB at "
                       f"`{d['site']}` (axis {d['axis']})")
        out.append("")
    return "\n".join(out)


if __name__ == "__main__":
    main()
