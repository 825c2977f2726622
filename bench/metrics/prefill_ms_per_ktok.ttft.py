"""Device time of the engine's chunked-prefill programs per thousand prompt
tokens they prefilled in the traced window.

A chunk program is one of the engine's programs (the same jitted name as
its decode program) that ran no decode kernel.  The prompt tokens are
estimated from the requests: each request's prompt is spread evenly over
the stretch from its admission to its first token, and the part of that
stretch inside the window counts (tokens the radix index served count
too)."""

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    decode = win.trace.modules_with(KERNEL)
    names = {n for n, _, _ in decode}
    starts = {s for _, s, _ in decode}
    chunk = [m for m in win.trace.modules
             if m[0] in names and m[1] not in starts]
    if not chunk:
        return None
    tokens = 0.0
    for r in win.records:
        a, b = r.t_admit, r.stamps[0]
        if b <= a:
            continue
        inside = max(0.0, min(b, win.t1) - max(a, win.t0))
        tokens += len(r.prompt) * inside / (b - a)
    if tokens <= 0:
        return None
    return sum(d for _, _, d in chunk) / 1e6 / (tokens / 1000.0)
