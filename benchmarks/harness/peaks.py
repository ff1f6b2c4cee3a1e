"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
HBM, 16 GB).  An unknown kind is an error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on file for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(work, peaks):
    """Roofline floor: the larger of operations over peak and bytes over
    peak.  Returns (seconds, which bound applies)."""
    by_ops = work["ops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops else (by_ops, "compute")
