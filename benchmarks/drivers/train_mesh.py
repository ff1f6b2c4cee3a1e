"""Training driver for a cell over every chip of a host: ``train.py``'s run,
read per chip.

The run is ``train.run``'s, unchanged: one ``lgb.train`` call timed from
inside by ``train.Window``, the reference following the first trees on all
rows, ``plain_tree`` and ``check.check_training``.  Three things differ:

- ``memory`` is the FULLEST device's ``memory_stats()`` (in use plus
  reserved, as ``train.py`` counts one chip), so ``peak_hbm_share`` and the
  floor the driver judges a cell by are the fullest chip's; the context
  carries every device's peak (``device_peaks``), which
  ``hbm_device_spread`` reads;
- the work behind ``tree_mfu`` (``step``) is divided by the device count:
  its time is the window's wall clock, which every chip spends at once.
  The histogram's and the partition's work stay the whole job's, because
  the trace sums the chips' device time (``harness/trace.py`` reads every
  ``/device:`` plane), so their rooflines read a chip's share;
- before the first tree it asks the program where it put the scores: a
  program that keeps them whole on one device cannot hold this cell's
  per-row state evenly, and the run ends there, with that said, in place
  of growing trees at a rate nothing can wait for (the parent of PR 38).
"""
from benchmarks.drivers import train

SCOPES_PROGRAM = train.SCOPES_PROGRAM


def placement_fault(scores, devices):
    """Why ``scores`` [k, N] is not evenly row-sharded over ``devices``,
    or None."""
    shards = scores.addressable_shards
    held = {s.device for s in shards}
    if held != set(devices):
        return (f"the scores live on {len(held)} of the {len(devices)} "
                f"devices")
    widths = {s.data.shape[-1] for s in shards}
    if widths != {scores.shape[-1] // len(devices)}:
        return (f"a device holds {sorted(widths)} of the {scores.shape[-1]} "
                f"rows' scores, where an even share is "
                f"{scores.shape[-1] // len(devices)}")
    return None


def run(cell, seed, seconds, trace, t_process, say, trace_dir):
    import jax
    import lightgbm_tpu as lgb
    devices = jax.devices()[:cell["chips"]]
    update = lgb.Booster.update
    first = []

    def checked(self, *args, **kwargs):
        if not first:
            first.append(True)
            fault = placement_fault(self.inner.scores, devices)
            if fault:
                raise SystemExit(
                    f"bench: {cell['name']} needs every per-row array "
                    f"sharded evenly over the {len(devices)} chips, and "
                    f"{fault}: this program cannot run the cell")
        return update(self, *args, **kwargs)

    lgb.Booster.update = checked
    try:
        out = train.run(cell, seed, seconds, trace, t_process, say,
                        trace_dir)
    finally:
        lgb.Booster.update = update

    stats = [d.memory_stats() or {} for d in devices]  # None off the chip
    peaks = [int(s.get("peak_bytes_in_use", 0))
             + int(s.get("peak_bytes_reserved", 0)) for s in stats]
    full = max(range(len(devices)), key=peaks.__getitem__)
    say("memory peaks by device: " + " ".join(
        f"{d.id}={p}" for d, p in zip(devices, peaks)))
    out["memory"] = {
        "peak_bytes": peaks[full],
        "allocated_peak_bytes": int(stats[full].get("peak_bytes_in_use", 0)),
        "reserved_peak_bytes": int(stats[full].get("peak_bytes_reserved", 0)),
        "limit_bytes": int(stats[full].get("bytes_limit", 0)),
    }
    ctx = out["context"]
    ctx["devices"] = len(devices)
    ctx["device_peaks"] = peaks
    step = ctx["work"].get("step")
    if step:
        ctx["work"]["step"] = {k: v / len(devices) for k, v in step.items()}
    return out
