"""The fold's share of its memory roofline (%): the bytes an exact count
must move (``bench/fold_bytes.py``, from the reference's answers) over the
HBM bandwidth of the chips the fold runs on (the chip's bandwidth times
the cell's chips), divided by the fold's device time per chip in the
trace.  The requests and the trace cover the same interval: every
request sent from the window's start, each answered by a fold the trace
holds."""

from bench import fold_bytes as _fb


def read(rec):
    t = rec.trace
    bw = rec.peaks.get("hbm_bytes_per_s")
    if not t or not t["fold_device_s"] or not bw or rec.want is None:
        return None
    r = rec.requests
    done = r.qid[r.count >= 0]
    need = _fb.needed_bytes(rec.arities[done], rec.want[done])
    return 100.0 * need / (bw * rec.chips) / t["fold_device_s"] if need else None
