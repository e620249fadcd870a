"""Self time of the codec's spans, frame encoding and decoding, in the
traced span, in ms per reconciliation traced."""

SPANS = ("repro.wire.encode", "repro.wire.decode")


def read(w):
    return w.self_ms_per_recon(SPANS)
