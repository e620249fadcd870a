"""Host spans of the reconciliation path, one name per layer.

A span is a :class:`jax.profiler.TraceAnnotation`.  It records only while
a profiler session is active (``jax.profiler.trace``, or
``start_trace``/``stop_trace``), into the same capture and on the same
clock as the device's ops, so every idle stretch of the device can be put
down to the host work that covers it.  With no session active a span
costs about a microsecond.

Every span carries the identifier of its request: ``peer=<registration
index>`` on per-peer spans, ``tick=<n>`` on the engine's tick.

Spans, with their layer:

* ``repro.tick`` (engine): one plan/execute iteration of the tick loop;
* ``repro.serve`` (stream): fetching one peer's window(s) from its stream;
* ``repro.wire.encode`` (codec): frame encoding, inside ``repro.serve``;
* ``repro.wire.decode`` (codec): frame decoding on ingest;
* ``repro.absorb`` (absorb): local subtract and chain walk of one round;
* ``repro.plan`` (staging): bucketing a tick's units by shape;
* ``repro.stage`` (staging): the host's rows, host→device copy, dispatch;
* ``repro.wait`` (staging): blocked on the device, device→host copies;
* ``repro.unstage`` (staging): fetched arrays → ``DeviceDecodeResult``\\ s;
* ``repro.merge`` (absorb): folding a peer's device results in;
* ``repro.host_peel`` (host peel): the exact host peel;
* ``repro.report`` (engine): building the reports.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

TICK = "repro.tick"
SERVE = "repro.serve"
WIRE_ENCODE = "repro.wire.encode"
WIRE_DECODE = "repro.wire.decode"
ABSORB = "repro.absorb"
PLAN = "repro.plan"
STAGE = "repro.stage"
WAIT = "repro.wait"
UNSTAGE = "repro.unstage"
MERGE = "repro.merge"
HOST_PEEL = "repro.host_peel"
REPORT = "repro.report"

NAMES = (TICK, SERVE, WIRE_ENCODE, WIRE_DECODE, ABSORB, PLAN, STAGE, WAIT,
         UNSTAGE, MERGE, HOST_PEEL, REPORT)


# Name scopes of the device peel's ops (``jax.named_scope``): a device op's
# ``tf_op`` path in the profiler trace holds the scope it was traced in.
HASH_SCOPE = "repro.hash"     # SipHash of the rows: purity scan, map seeds
APPLY_SCOPE = "repro.apply"   # chain removal of a wave's recovered rows


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name`` with ``args`` as its trace arguments; use it as
    a context manager.  Arguments known only inside the span are added
    with ``set_metadata(**more)`` on the object it yields."""
    return TraceAnnotation(name, **args)
