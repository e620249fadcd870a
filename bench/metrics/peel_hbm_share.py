"""The batched device peel's share of the HBM roofline in the traced span:
the bytes its waves must move at the least (``bench/roofline.py``, from
the reports' ``device_symbols``, ``device_waves`` and the row width), over
the time of the ``jit_peel_batched`` program's module events, in % of the
device's HBM bandwidth.  A program whose reports lack the counters, or a
span with no such program, reads nothing."""
from bench import roofline


def read(w):
    if w.trace is None:
        return None
    ns = sum(t for name, t in w.trace.programs.items()
             if name.startswith(roofline.PEEL_PROGRAM))
    reports = [rep for r in w.traced_rounds if r.reports for rep in r.reports]
    moved = roofline.peel_bytes(reports)
    if not ns or not moved:
        return None
    import jax
    return roofline.hbm_share(moved, ns / 1e9, jax.devices()[0].device_kind)
