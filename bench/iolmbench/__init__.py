"""The chip benchmark of the IOLM-DB reproduction.

Everything that measures lives here, apart from the program: the
traffic generator, the weight maker, the plain reference that decides
``correct``, the reduction from a profiler trace to device numbers,
the operation and byte counts of each kernel, and the peaks table.
The program under test (``src/repro``) is imported only to build the
system and to drive it.

A cell is found by name: ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py``; each kernel's operations and bytes are
in ``bench/kernels/<kernel>.py``.
"""
