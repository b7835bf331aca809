"""fft_ms_per_matvec: device time of the FFT pair per operator application
(ms), over the applications that end inside the traced window
(bench.trace.per_application)."""

from bench import trace


def read(facts: dict):
    seconds = trace.per_application(facts, "fft")
    return None if not seconds else 1e3 * seconds
