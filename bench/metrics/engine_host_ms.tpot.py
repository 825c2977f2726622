"""The engine entry's own host time in a decode quantum: the mean over
decode quanta of the ``worker.entry`` span less the ``engine.sync`` spans
inside it (device-to-host reads, where the entry waits for the device),
in ms: unpacking the payload, block accounting, dispatching the decode
program and packing the result."""

import spans


def read(win):
    if win.trace is None:
        return None
    q = spans.decode_quanta(win.trace)
    if not q:
        return None
    return sum(e[2] - sum(s[2] for s in syn)
               for _, e, syn in q) / len(q) / 1e6
