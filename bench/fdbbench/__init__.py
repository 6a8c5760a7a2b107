"""The FDB benchmark harness: one cell of ``BENCHMARK.json``, run once.

Everything that measures lives here, apart from the program: traffic
generation (:mod:`.loop`), the source fields (:mod:`.fields`), the plain
reference codec and the comparison that decides ``correct``
(:mod:`.reference`), the reduction of the profiler's trace
(:mod:`.xtrace`), the roofline byte functions and the table of peaks
(:mod:`.roofline`), and the readers of spans (:mod:`.spans`).  A cell is
found by name: its configuration in ``bench/configs/<config>.json``, its
traffic in ``bench/traffic/<traffic>.json`` and each per-layer metric in
``bench/metrics/<metric>.py`` (:mod:`.spec`).
"""
