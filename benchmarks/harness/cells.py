"""Find a cell's files by the names in BENCHMARK.json: nothing here knows a
configuration, a traffic mix or a metric by name."""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace(".py", "").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name, bench=None):
    """{"name", "chips", "config", "traffic", "end_to_end", "per_layer"}"""
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(CHECKOUT, cfg_entry["file"])) as f:
        config = json.load(f)

    def reported(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": int(w["chips"]), "config": config,
        "traffic": load_json("traffic", w["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
    }
