"""The program's process-wide counter registry, read after the run; None
from a program that has none, or not this counter.  The first reading says
the two tables the set-up metrics come from on standard error, so that a
run's log carries them (``program phase seconds:``, ``program compile
seconds:``)."""
import sys

_said = []


def counter(name):
    try:
        from lightgbm_tpu.obs.counters import counters
    except ImportError:
        return None
    if not _said:
        _said.append(True)
        say_tables(counters)
    return counters.get(name) or None


def phase_seconds(phase):
    """``phase_seconds{phase=...}``: seconds inside that ``lgb:`` span."""
    return (counter("phase_seconds") or {}).get(f"phase={phase}")


def _tags(key):
    return dict(kv.split("=", 1) for kv in key.split(",") if "=" in kv)


def say_tables(counters, top=10):
    out = sys.stderr
    calls = counters.get("phase_calls")
    phases = " ".join(
        f"{_tags(k)['phase']}={v:.3f}/{int(calls.get(k, 0))}"
        for k, v in sorted(counters.get("phase_seconds").items(),
                           key=lambda kv: -kv[1]))
    by_fun = {}
    for key, v in counters.get("compile_seconds").items():
        tags = _tags(key)
        by_fun.setdefault(tags["fun"], {})[tags["stage"]] = v
    calls = counters.get("compile_calls")
    funs = sorted(by_fun.items(), key=lambda kv: -sum(kv[1].values()))
    compiles = " ".join(
        f"{fun}={sum(st.values()):.3f}(trace {st.get('trace', 0):.3f} "
        f"lower {st.get('lower', 0):.3f} backend {st.get('backend', 0):.3f})"
        f"/{int(calls.get('fun=' + fun, 0))}" for fun, st in funs[:top])
    rest = sum(sum(st.values()) for _, st in funs[top:])
    print(f"bench: program phase seconds: {phases or '(none)'} "
          f"(seconds/calls, whole process)", file=out)
    print(f"bench: program compile seconds: {compiles or '(none)'} "
          f"and {len(funs[top:])} more={rest:.3f}; cache hits "
          f"{int(counters.total('compile_cache_hits'))} "
          f"(seconds(by stage)/calls, whole process)", file=out, flush=True)
