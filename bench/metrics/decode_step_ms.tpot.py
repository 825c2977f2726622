"""Device time of the engine's paged decode programs per decode step.

A decode program is one that ran the paged decode-attention kernel; the
kernel runs once per layer per step, so the steps are counted from the
kernel's operations in the trace."""

KERNEL = "flash_decode_paged"


def read(win):
    if win.trace is None:
        return None
    progs = win.trace.modules_with(KERNEL)
    steps = win.trace.op_count(KERNEL) / win.config["num_hidden_layers"]
    if not progs or steps <= 0:
        return None
    return sum(d for _, _, d in progs) / 1e6 / steps
