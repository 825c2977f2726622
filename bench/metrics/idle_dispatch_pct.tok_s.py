"""Device idle time spent in the invocation layer, as a share of the
traced window: idle instants whose innermost program span is an engine
call (inside the call, outside its ``worker.entry``)."""

import spans


def read(win):
    if win.trace is None or win.trace.window_s <= 0:
        return None
    if not any(e[0] in spans.ENGINE_CALLS for e in spans.program(win.trace)):
        return None
    idle = spans.idle_by_span(win.trace)
    return 100.0 * sum(idle.get(n, 0.0) for n in spans.ENGINE_CALLS) \
        / win.trace.window_s
