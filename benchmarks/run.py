"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and prints
one JSON object as the last line of standard output.  No accelerator, or
fewer chips than the cell asks for: exit 3 and no result line.
"""
import time
T_PROCESS = time.perf_counter()

import argparse                                          # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import sys                                               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)


def say(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def gate(chips):
    """The device as jax reports it, or exit: a result comes from a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"needs {chips} TPU chip(s); jax reports {len(devs)} x "
            f"{devs[0].platform!r} — no accelerator, no result")
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(cell, seed, seconds, trace, device, t_process=None,
             trace_dir=None):
    """Everything a run does after the look for a chip; returns the result
    object.  ``tests/`` call this with a small cell on the CPU."""
    from benchmarks.harness import cells, compare, metrics, peaks
    from benchmarks.harness import trace as trace_mod
    t_process = time.perf_counter() if t_process is None else t_process
    trace_dir = trace_dir or os.path.join(
        CHECKOUT, ".bench_cache", "trace", cell["name"])
    driver = cells.load_module("drivers", cell["traffic"]["driver"] + ".py")
    out = driver.run(cell, seed, seconds, trace, t_process, say, trace_dir)

    result_device = dict(device,
                         memory_peak_bytes=out["memory"]["peak_bytes"])
    values = dict(out["end_to_end"])
    listed = cell["end_to_end"]
    breakdown = None
    if trace:
        listed = cell["per_layer"]
        names = [m["name"] for m in listed]
        ctx = dict(out["context"], memory=out["memory"], device=device,
                   peaks=peaks.PEAKS.get(device["kind"]), trace=None)
        t = time.perf_counter()
        events = trace_mod.load_events(trace_dir)
        reduced = trace_mod.reduce_trace(
            events, metrics.scopes_wanted(names), ctx["program"])
        say(f"trace: {len(events)} events reduced in "
            f"{time.perf_counter() - t:.1f} s")
        if reduced:
            ctx["trace"] = reduced
            result_device.update(busy_s=reduced["busy_s"],
                                 window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        values = {n: metrics.read_metric(n, ctx) for n in names}
    units = {m["name"]: m["unit"] for m in listed}
    rows, ok = compare.verdict(out["numbers"], out["limits"])
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit, _ in rows}
    result = {
        "correct": bool(ok), "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {n: {"value": v, "unit": units[n]}
                    for n, v in values.items() if n in units and v is not None},
        "device": result_device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["read_not_compared"] = {n: out["numbers"][n]
                                   for n in compare.NOT_COMPARED
                                   if n in out["numbers"]}
    result["compared"] = compared
    for name in compare.NOT_COMPARED:
        if name in out["numbers"]:
            say(f"read, not compared: {name} = {out['numbers'][name]:.6g}")
    for name, value, limit, good in rows:
        say(f"compared {name} = {value:.6g} limit {limit} "
            f"{'ok' if good else 'FAILED'}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(CHECKOUT, "lightgbm_tpu")):
        say("no program beside the benchmark: nothing to measure")
        raise SystemExit(4)
    from benchmarks.harness import cells
    cell = cells.cell(args.workload)
    device = gate(cell["chips"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, T_PROCESS)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
