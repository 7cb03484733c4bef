"""Seconds spent in the sharded solver's constructor, ``ShardedLBM(...)``,
until its tables and state are on the devices (host clock): the slab plan
and the slabs' tilings, the step tables, their placement and the initial
state.  Nothing to read from a driver that builds no sharded solver."""


def read(run):
    return getattr(run, "shard_build_s", None)
