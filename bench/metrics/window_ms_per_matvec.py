"""window_ms_per_matvec: device time of the window step (spread and
gather, either backend, with the fold, roll and pad around them) per
operator application (ms), over the applications that end inside the
traced window (bench.trace.per_application)."""

from bench import trace


def read(facts: dict):
    seconds = trace.per_application(facts, "window")
    return None if not seconds else 1e3 * seconds
