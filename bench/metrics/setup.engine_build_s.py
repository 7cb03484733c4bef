"""Seconds spent in the solver's constructor, ``SparseTiledLBM(...)``, until
its state and tables are on the device (host clock): tiling, streaming
tables, backend tables and their placement."""


def read(run):
    return run.engine_build_s
