"""Engine plan/execute ticks (or a lone session's windows) per
reconciliation, over the window (``ReconcileEngine.ticks``)."""


def read(w):
    return w.total("ticks") / w.recons if w.recons else None
