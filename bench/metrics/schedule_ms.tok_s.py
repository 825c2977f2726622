"""The engine loop's own work per pass: time in ``engine.schedule`` spans
(pruning, admission, the completion sweep, token stamping, between engine
calls) over the number of ``engine.decode_quantum`` spans, in ms."""

import spans


def read(win):
    if win.trace is None:
        return None
    n = len(spans.named(win.trace, "engine.decode_quantum"))
    if not n:
        return None
    return spans.seconds(spans.named(win.trace, "engine.schedule")) \
        * 1000.0 / n
