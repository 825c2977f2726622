"""Mean live rows per decode chunk over the traced window, from the
batcher's counters (``BatcherStats.occupancy_sum / decode_chunks``)."""


def read(win):
    n = win.counters.get("decode_chunks", 0)
    return win.counters["occupancy_sum"] / n if n else None
