"""Median completion time of all reconciliations in the window, failed
ones included."""
import statistics


def read(w):
    return statistics.median(w.sync_ms())
