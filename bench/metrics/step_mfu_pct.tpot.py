"""Share of the chip's bf16 peak that the decode steps reach: the model
FLOPs of every token decoded in the traced window (weights of the live rows
and attention over their live lengths, ``bench/costs.py``) over the device
time of the paged decode programs."""

import costs

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    busy = sum(d for _, _, d in win.trace.modules_with(KERNEL)) / 1e9
    work = costs.decode_work(win.config, win.records, win.t0, win.t1)
    if busy <= 0 or not work["tokens"]:
        return None
    return 100.0 * work["flops"] / busy / win.peaks["bf16_flops_per_s"]
