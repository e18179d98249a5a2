"""The benchmark's own library: loading, traffic, statistics, traces, work counts.

Nothing here is imported by the program under test. What belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``.
"""
