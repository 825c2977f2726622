"""The invocation layer's share of a decode quantum: the mean over decode
quanta of the ``engine.decode_quantum`` span (the loop thread, from before
its hand-off to the pack thread to after the reply is folded in) less the
``worker.entry`` span inside it (the worker thread running the entry), in
ms.  What is left is packing, ``Function.submit``, the pool's queue,
unpacking and the hop back to the event loop."""

import spans


def read(win):
    if win.trace is None:
        return None
    q = spans.decode_quanta(win.trace)
    if not q:
        return None
    return sum(c[2] - e[2] for c, e, _ in q) / len(q) / 1e6
