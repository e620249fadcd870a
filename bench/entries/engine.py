"""One round through ``ReconcileEngine``: every replica of the round is
registered as a fresh wire-mode session, then one pipelined ``run()``
drives them all to completion.  The round's counts are the engine's
ticks and batched device dispatches."""
from __future__ import annotations


def run_round(stream, locals_, session, span):
    """``session(local)`` makes one peer's ``Session``; ``span(name)`` is a
    host trace span.  Returns the reports in order and the round's counts."""
    from repro.protocol import ReconcileEngine

    engine = ReconcileEngine()
    with span("bench.register"):
        for local in locals_:
            engine.register(stream, session(local), wire=True)
    with span("bench.run"):
        reports = engine.run()
    return reports, {"ticks": engine.ticks, "dispatches": engine.dispatches}
