"""128-lane row reads of the fold's segment searches per padded cell
per stage, over the batches dispatched in the window: Σ ``search_reads``
/ Σ (``cells`` × stages) of the program's per-batch ``info``.  A program
that does not count the reads gives nothing to read."""


def read(rec):
    b = [b.info for b in rec.window_batches()
         if b.info.get("search_reads") and b.info.get("stages")]
    if not b:
        return None
    return sum(i["search_reads"] for i in b) / sum(i["cells"] * len(i["stages"]) for i in b)
