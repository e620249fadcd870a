"""Batched device dispatches per tick over the window: how many shape
buckets split a tick (``ReconcileEngine.dispatches / ticks``)."""


def read(w):
    ticks = w.total("ticks")
    return w.total("dispatches") / ticks if ticks else None
