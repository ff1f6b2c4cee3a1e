"""Operations and bytes that growing a tree needs, from the data's shape and
the grown tree's own node counts alone: no kernel name, tile or padding.

A tree is given as (left_child, right_child, internal_count, leaf_count) in
LightGBM's layout: internal node i is the i-th split, a child c < 0 is leaf
``~c``.  ``shape`` is {"rows", "columns", "bins", "bin_bytes"}.

Per histogram built over ``r`` rows: read every column's bin (``bin_bytes``
each), the gradient and the hessian (4 bytes each); 2 additions per row and
column; write columns x bins x (g, h, count) x 4 bytes.  The root builds one
over all rows; every split builds one over its smaller child (the sibling is
parent minus child: 1 subtraction per entry, parent read, sibling written).
Per split, the partition reads and writes the parent's row ids (4 bytes
each way) and reads the split column's bin: one comparison per row.
Per tree, gradients and score: read score and label, write g and h; then
read score and leaf id, write score — 28 bytes and 8 operations a row.
"""

G_H_BYTES = 8
HIST_ENTRY_BYTES = 12        # g, h, count as 4-byte numbers
ROW_ID_BYTES = 4
SCORE_PASS_BYTES = 28
SCORE_PASS_OPS = 8


def _count(child, internal_count, leaf_count):
    return int(leaf_count[~child]) if child < 0 else int(internal_count[child])


def tree_rows(left_child, right_child, internal_count, leaf_count):
    """(rows histogrammed, rows partitioned, splits) for one tree."""
    n_splits = len(left_child)
    if n_splits == 0:
        return 0, 0, 0
    hist_rows = int(internal_count[0])          # the root
    part_rows = 0
    for i in range(n_splits):
        lc = _count(int(left_child[i]), internal_count, leaf_count)
        rc = _count(int(right_child[i]), internal_count, leaf_count)
        hist_rows += min(lc, rc)
        part_rows += lc + rc
    return hist_rows, part_rows, n_splits


def tree_work(shape, left_child, right_child, internal_count, leaf_count):
    """{"histogram": {"ops", "bytes"}, "partition": {...}, "step": {...}}"""
    hist_rows, part_rows, n_splits = tree_rows(
        left_child, right_child, internal_count, leaf_count)
    cols, bins = shape["columns"], shape["bins"]
    table = cols * bins * HIST_ENTRY_BYTES
    n_hist = 1 + n_splits if n_splits else 0
    hist = {
        "rows": hist_rows,
        "bytes": hist_rows * (cols * shape["bin_bytes"] + G_H_BYTES)
                 + n_hist * table + n_splits * 2 * table,
        "ops": hist_rows * cols * 2 + n_splits * cols * bins * 3,
    }
    part = {
        "rows": part_rows,
        "bytes": part_rows * (2 * ROW_ID_BYTES + shape["bin_bytes"]),
        "ops": part_rows,
    }
    step = {
        "bytes": hist["bytes"] + part["bytes"]
                 + shape["rows"] * SCORE_PASS_BYTES,
        "ops": hist["ops"] + part["ops"] + shape["rows"] * SCORE_PASS_OPS,
    }
    return {"histogram": hist, "partition": part, "step": step}


def add_work(total, one):
    for layer, w in one.items():
        t = total.setdefault(layer, {})
        for k, v in w.items():
            t[k] = t.get(k, 0) + v
    return total
