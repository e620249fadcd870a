"""One round through ``run_session``: each replica of the round
reconciles alone, in turn, over wire frames.  A lone plain session takes
the non-pipelined engine's single-unit decode, the Pallas kernels on a
TPU.  The round's counts are the sessions' windows and device decodes."""
from __future__ import annotations


def run_round(stream, locals_, session, span):
    from repro.protocol import run_session

    reports = []
    for local in locals_:
        with span("bench.register"):
            s = session(local)
        with span("bench.run"):
            reports.append(run_session(stream, s, wire=True))
    return reports, {"ticks": sum(r.grow_steps for r in reports),
                     "dispatches": sum(r.device_decodes for r in reports)}
