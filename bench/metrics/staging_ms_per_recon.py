"""Self time of the staging spans, bucketing, host padding and copies to
the device, and unpacking fetched arrays, in the traced span, in ms per
reconciliation traced.  The wait on the device (``repro.wait``) is not
staging's own time."""

SPANS = ("repro.plan", "repro.stage", "repro.unstage")


def read(w):
    return w.self_ms_per_recon(SPANS)
