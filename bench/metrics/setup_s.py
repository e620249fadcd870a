"""Seconds from process start to the window: imports, data, the stream
caches, the replica pool and the warm-up rounds (compiles, or loads from
the persistent cache)."""


def read(w):
    return w.setup_s
