"""Load skew of the sharded fold (ratio): over the batches dispatched in
the window, the mean of the largest over the mean true cells per shard,
from the ``shard_cells`` of the program's per-batch ``info``.  1 is an
even split; every chip runs the fold at the largest shard's shape."""

import numpy as np


def read(rec):
    v = [max(c) / np.mean(c) for c in (b.info.get("shard_cells") for b in rec.window_batches())
         if c and sum(c) > 0]
    return float(np.mean(v)) if v else None
