"""Share of rows admitted in the traced window whose prompt head came from
the engine's radix index (``BatcherStats.prefix_hits`` against misses)."""


def read(win):
    hits = win.counters.get("prefix_hits", 0)
    total = hits + win.counters.get("prefix_misses", 0)
    return 100.0 * hits / total if total else None
