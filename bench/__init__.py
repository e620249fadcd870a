"""The chip benchmark of the reconciliation service: ``python3 bench/run.py``.

Everything that belongs to one deployment, traffic mix, entry or metric
sits in a file of its own and is found by the name ``BENCHMARK.json``
gives it: ``configs/<config>.json``, ``traffic/<mix>.json``,
``entries/<entry>.py`` and ``metrics/<base>.py``.
"""
