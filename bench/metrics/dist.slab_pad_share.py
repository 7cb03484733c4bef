"""Share of the tile rows each chip steps that its slab does not own, in
percent: 1 - the tiles a slab owns on average / the rows every chip holds
(``t_pad``: owned, halo and padding rows and the dummy row).  Every chip
runs the same padded step, so this is the work the slab plan adds to the
domain's own: its halo layers and its imbalance.  Read from the solver's
gauges ``dist.slab.own_tiles_mean`` and ``dist.slab.t_pad``, set when it is
built (program counter).  Nothing to read where the solver sets neither."""


def read(run):
    gauges = getattr(run, "gauges", None) or {}
    mean = gauges.get("dist.slab.own_tiles_mean")
    t_pad = gauges.get("dist.slab.t_pad")
    if mean is None or not t_pad:
        return None
    return 100.0 * (1.0 - mean / t_pad)
