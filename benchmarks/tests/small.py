"""A cell at a size a test run can hold: the real cell's files, fewer rows
and leaves.  The chip is not looked for; the device entry says so."""
import copy
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness import cells  # noqa: E402

NO_CHIP = {"platform": "none", "kind": "test on the CPU", "count": 0}


def small_cell(config="higgs", traffic="train", rows=60000):
    """Built from the two files by name, as ``cells.cell`` does for a cell
    that BENCHMARK.json lists (``train-eval`` has no cell there yet)."""
    bench = cells.benchmark()
    cell = {"name": f"{config}.{traffic}", "chips": 1,
            "config": cells.load_json("configs", config + ".json"),
            "traffic": cells.load_json("traffic", traffic + ".json"),
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
    cell = copy.deepcopy(cell)
    cell["config"].update(rows=rows, valid_rows=rows // 10)
    cell["config"]["params"].update(num_leaves=31,
                                    min_sum_hessian_in_leaf=10)
    cell["traffic"]["score_sample_rows"] = 5000
    return cell
