"""Device: percent of the traced window in which no program ran on the chip
(profiler trace, averaged over the chips)."""


def read(w):
    return 100.0 * w.trace.idle_share if w.trace is not None else None
