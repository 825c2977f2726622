"""Share of its roofline that the paged decode-attention kernel reaches:
the least time the chip needs for the attention of every token decoded in
the traced window, over the live lengths and not the padded tiles
(``bench/costs.py``), against the kernel's device time."""

import costs

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    t = win.trace.op_time_s(KERNEL)
    work = costs.decode_work(win.config, win.records, win.t0, win.t1)
    if t <= 0 or not work["tokens"]:
        return None
    least, _ = costs.roofline_s(*work["attention"], win.peaks)
    return 100.0 * least / t
