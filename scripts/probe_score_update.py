"""Chip probe for the score update: what it costs to add each row's leaf
value to its score, ``scores + lr * leaf_values[row_leaf]``, in the forms
``boosting.leaf_value_of_rows`` chooses between, and so where the choice
(``boosting.SELECT_MAX_LEAVES``) belongs.

  select   the binary tree of selects on the leaf id's bits (L - 1 selects
           and log2 L bit tests a row, one loop fusion over the rows)
  gather   ``leaf_values[row_leaf]``: one element gathered an index
  chain    for the record: an unrolled compare-and-select chain over the
           leaves (L compares and L selects a row), at the smaller counts

For each leaf count, each form is the program itself (``boosting._update_score``
with the choice forced, ``chain`` the same wrapper around the chain) lowered
and compiled ahead (compile seconds, temporaries and code bytes from the
compiled program, and whether its text holds a ``gather``), then called K
times with each call's scores feeding the next, one host clock around the
K calls and one ``block_until_ready``, divided by K: milliseconds a call.
Leaf ids are uniform over the leaves, values standard normal.

    python scripts/probe_score_update.py [rows] [leaf counts]

Writes one JSON dict to stdout and to ``chiprun_out/probe_score_update.json``.
Off the TPU it only checks that the forms agree bit for bit (pass a small
row count) and writes no timing.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu import boosting

CHAIN_MAX_LEAVES = 255      # its compile grows past a minute by 4095


def chain(leaf_values, row_leaf):
    out = jnp.broadcast_to(leaf_values[0], row_leaf.shape)
    for i in range(1, leaf_values.shape[0]):
        out = jnp.where(row_leaf == i, leaf_values[i], out)
    return out


@jax.jit
def chain_update(scores_k, leaf_values, row_leaf, lr):
    with jax.named_scope("score_update"):
        return scores_k + lr * chain(leaf_values, row_leaf)


def program(form):
    """The jitted update in ``form``, its trace cache emptied so that the
    forced choice is the one traced."""
    if form == "chain":
        return chain_update
    boosting.SELECT_MAX_LEAVES = 1 << 30 if form == "select" else 0
    boosting._update_score.clear_cache()
    return boosting._update_score


def compiled(form, args):
    fn = program(form)
    t0 = time.perf_counter()
    exe = fn.lower(*args).compile()
    secs = time.perf_counter() - t0
    mem = exe.memory_analysis()
    return exe, {"compile_s": secs,
                 "temp_bytes": int(mem.temp_size_in_bytes),
                 "code_bytes": int(mem.generated_code_size_in_bytes),
                 "gather_in_text": "gather" in exe.as_text()}


def ms_per_call(exe, scores, lv, rl, lr, k):
    scores = jax.block_until_ready(exe(scores, lv, rl, lr))
    t0 = time.perf_counter()
    for _ in range(k):
        scores = exe(scores, lv, rl, lr)
    jax.block_until_ready(scores)
    return (time.perf_counter() - t0) / k * 1e3


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
    counts = ([int(x) for x in sys.argv[2].split(",")] if len(sys.argv) > 2
              else [31, 255, 1023, 4095])
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": n, "forms": {}}
    rng = np.random.default_rng(39)
    scores = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    lr = jnp.float32(0.1)
    keep = boosting.SELECT_MAX_LEAVES
    try:
        for L in counts:
            lv_h = rng.standard_normal(L).astype(np.float32)
            lv_h[:3] = [np.nan, -np.inf, -0.0][:L]
            lv = jnp.asarray(lv_h)
            rl = jnp.asarray(rng.integers(0, L, n, dtype=np.int32))
            forms = ["select", "gather"] + (["chain"] if L <= CHAIN_MAX_LEAVES
                                            else [])
            want = None
            for form in forms:
                exe, row = compiled(form, (scores, lv, rl, lr))
                got = np.asarray(exe(scores, lv, rl, lr)).view(np.uint32)
                if want is None:
                    want = got
                assert np.array_equal(got, want), (form, L)
                if dev.platform == "tpu":
                    ms = ms_per_call(exe, scores, lv, rl, lr, 20)
                    row.update(ms=ms, ns_per_row=ms * 1e6 / n)
                res["forms"][f"{L}.{form}"] = row
                print(f"L={L:5d} {form:7s} " + " ".join(
                    f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in row.items()), file=sys.stderr, flush=True)
    finally:
        boosting.SELECT_MAX_LEAVES = keep
        boosting._update_score.clear_cache()
    if dev.platform == "tpu":
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_score_update.json", "w") as fh:
            json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
