"""Share of the traced span in which no operation ran on the device, in
percent, from the profiler trace (``bench/trace.py``)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share
