"""Device time of whole programs (the trace's module events) in the traced
span, per reconciliation completed in it."""


def read(w):
    recons = sum(len(r.replicas) for r in w.traced_rounds)
    if w.trace is None or not recons or not w.trace.program_ns:
        return None
    return w.trace.program_ns / 1e6 / recons
