"""How unevenly the chips of one run are filled: the fullest device's peak
(in use plus reserved, ``memory_stats()``) less the emptiest's, over the
fullest's, in %.  Read where the driver lists every device's peak
(``drivers/train_mesh.py``); None elsewhere or off the chip."""


def read(ctx):
    peaks = ctx.get("device_peaks")
    if not peaks or not max(peaks):
        return None
    return 100.0 * (max(peaks) - min(peaks)) / max(peaks)
