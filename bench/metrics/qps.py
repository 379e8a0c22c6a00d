"""Queries answered per second over the window: every batch's queries,
counted in proportion to the share of the batch's service (dispatch to
reply) that lies inside the window, over the window's wall seconds.  A
batch wholly inside counts in full; the one in flight at the close
counts for the part of it the window holds, so the count does not step
by a whole batch with where the close happens to fall."""


def read(rec):
    done = 0.0
    for b in rec.batches:
        inside = min(b.t_end, rec.t1) - max(b.t_start, rec.t0)
        if inside > 0:
            done += b.size * inside / (b.t_end - b.t_start)
    return done / (rec.t1 - rec.t0)
