"""Share of the fold's padded cells that carry no posting (%), over the
batches dispatched in the window: 100 × Σ(``cells`` − ``cells_true``) /
Σ ``cells`` of the program's per-batch ``info``."""


def read(rec):
    b = [b.info for b in rec.window_batches() if b.info.get("cells")]
    if not b:
        return None
    return 100.0 * sum(i["cells"] - i["cells_true"] for i in b) / sum(i["cells"] for i in b)
