"""Mean of one host-clock span of the program's per-batch ``info`` (ms)."""

import numpy as np


def mean_ms(rec, key: str):
    v = [b.info[key] for b in rec.window_batches() if b.info.get("n_kernel_calls")]
    return float(np.mean(v) * 1e3) if v else None
