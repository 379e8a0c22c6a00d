"""Host-to-device bytes per batch (MB, 1e6 bytes): the mean
``upload_bytes`` of the program's per-batch ``info`` over the batches
dispatched in the window."""


def read(rec):
    v = [b.info["upload_bytes"] for b in rec.window_batches() if b.info.get("upload_bytes")]
    return sum(v) / len(v) / 1e6 if v else None
