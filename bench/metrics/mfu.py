"""Useful (uncoded) model FLOPs of the images completed in the traced part
of the window, per second, over the chip's bf16 peak.  Coding redundancy
is not useful work."""


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    if not t or not peaks or not rec["completed_traced"] or not t["devices"]:
        return None
    flops = rec["geometry"].model_flops_per_image(rec["config"])
    rate = rec["completed_traced"] / rec["traced_s"]
    return 100.0 * flops * rate / (peaks["bf16_flops_per_s"] * t["devices"])
