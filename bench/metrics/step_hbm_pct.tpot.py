"""Share of the chip's HBM bandwidth that the decode steps reach: the bytes
a step must read (every weight once, the live rows' keys and values) over
the device time of the paged decode programs in the traced window."""

import costs

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    cfg = win.config
    busy = sum(d for _, _, d in win.trace.modules_with(KERNEL)) / 1e9
    steps = win.trace.op_count(KERNEL) / cfg["num_hidden_layers"]
    work = costs.decode_work(cfg, win.records, win.t0, win.t1)
    if busy <= 0 or steps <= 0 or not work["tokens"]:
        return None
    nbytes = steps * costs.matmul_params(cfg) * costs.BYTES \
        + work["kv_tokens"] * costs.kv_bytes_per_token(cfg)
    return 100.0 * nbytes / busy / win.peaks["hbm_bytes_per_s"]
