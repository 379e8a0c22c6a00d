"""Share of the cells the fold's stages after the first search that are
still live on entering the stage (%), over the batches dispatched in the
window: 100 × Σ ``late_live`` / Σ ``late_cells`` of the program's
per-batch ``info``.  The rest were ruled out by an earlier stage and
are searched all the same.  A program that does not count them, or a
window whose batches have no stage after the first, gives nothing to
read."""


def read(rec):
    b = [b.info for b in rec.window_batches() if b.info.get("late_cells")]
    if not b:
        return None
    return 100.0 * sum(i["late_live"] for i in b) / sum(i["late_cells"] for i in b)
