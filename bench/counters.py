"""Counts the benchmark reads from JAX while a window runs."""
from __future__ import annotations


class CompileCounter:
    """Counts backend compiles (and their seconds) while entered."""
    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self._EVENT:
            self.compiles += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def peak_bytes() -> int | None:
    """Peak device bytes on the fullest device, where the backend says.

    On a TPU ``peak_bytes_in_use`` counts buffers only: the runtime keeps
    each loaded program's temporaries apart, in ``peak_bytes_reserved``
    (on a TPU v5e, after one run of a program whose ``memory_analysis()``
    gives 3.46 GB of temporaries, ``peak_bytes_in_use`` read 24 MB and
    ``peak_bytes_reserved`` 3.45 GB).  The peak is the two together."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"] +
                         stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None
