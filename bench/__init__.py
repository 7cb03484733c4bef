"""On-chip benchmark of the sparse tiled LBM (the yardstick).

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell is made of is
found by name in files of its own: configurations (``configs/``), traffic
mixes (``traffic/``), the driver a mix names (``drivers/``), geometry
operations a configuration names (``geometry/``) and metric readers
(``metrics/``).  The reference that decides ``correct``, the peak table and
the trace reduction live here too, apart from the program under test.
"""
