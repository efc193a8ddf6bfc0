"""Canal chip benchmark: the yardstick shared by every cell.

``run.py`` (one directory up) is the entry point. This package holds
what no later change to the program may move: the lookup of cells,
configurations, traffic mixes and per-layer metrics by name
(:mod:`.registry`), the device check and peaks table (:mod:`.device`),
host spans and the in-window compile counter (:mod:`.spans`), the
reduction of a profiler trace to busy and idle time (:mod:`.tracing`),
and the independent check that decides ``correct``
(:mod:`.check_pnr`). Nothing here imports the
program at module import time.
"""
