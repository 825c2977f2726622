"""Share of the chip's HBM bandwidth that the decode steps reach: the bytes
a step must read (its weights once, for the mean live rows of a decode
chunk, and the live rows' keys and values) over the device time of the
paged decode programs in the traced window."""

import costs

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    cfg = win.config
    busy = sum(d for _, _, d in win.trace.modules_with(KERNEL)) / 1e9
    steps = win.trace.op_count(KERNEL) / cfg["num_hidden_layers"]
    chunks = win.counters.get("decode_chunks", 0)
    work = costs.decode_work(cfg, win.records, win.t0, win.t1)
    if busy <= 0 or steps <= 0 or not chunks or not work["tokens"]:
        return None
    rows = win.counters["occupancy_sum"] / chunks
    nbytes = steps * costs.step_weight_bytes(cfg, rows) \
        + work["attention"][1]
    return 100.0 * nbytes / busy / win.peaks["hbm_bytes_per_s"]
