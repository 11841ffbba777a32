"""Roofline share of the worker conv programs: the least time the chip
could take for the worker subtasks that ran in the window (per subtask the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth,
counted from the coded geometry) over the device seconds of the worker
programs."""


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    if not t or not peaks:
        return None
    secs, runs = t["worker_s"], t["worker_runs"]
    if secs <= 0 or not runs:
        return None
    geo = rec["geometry"]
    bound = 0.0
    for (layer, batch), count in runs.items():
        flops, nbytes = geo.subtask_work(rec["config"], layer, batch)
        bound += count * max(flops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
