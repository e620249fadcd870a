"""Reconciliations completed per second over the whole window."""


def read(w):
    return w.completed / w.seconds
