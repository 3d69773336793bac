"""The benchmark's own code: spec loading, traffic, spans, trace reduction,
work counts, the correctness comparison and the serving window.

Nothing here names a particular cell, configuration or metric: those live
in files of their own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the names in ``BENCHMARK.json``.
"""
