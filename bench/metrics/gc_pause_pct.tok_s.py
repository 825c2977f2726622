"""Python's garbage collector: time in ``py.gc`` spans (one per
collection, on whichever thread it interrupted) as a share of the traced
window.  Read only from a program that opens spans at all."""

import spans


def read(win):
    if win.trace is None or win.trace.window_s <= 0:
        return None
    found = spans.program(win.trace)
    if not found:
        return None
    gc = [e for e in found if e[0] == "py.gc"]
    return 100.0 * spans.seconds(gc) / win.trace.window_s
