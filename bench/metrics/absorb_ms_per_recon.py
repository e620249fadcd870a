"""Self time of the absorb layer's spans, a window's local subtract and
chain walk and the fold of device results, in the traced span, in ms per
reconciliation traced."""

SPANS = ("repro.absorb", "repro.merge")


def read(w):
    return w.self_ms_per_recon(SPANS)
