"""Rows of the device signature stores that their merges walk per update
batch: the `capacity` of each `store.merge_device` span, summed, over
the `maint.propagate` spans; 0 for batches that minted no novel key.
Read only where the program names its edge rewrites
(`maint.apply_edges`), as a program that has these spans does."""


def read(run):
    names = [s["name"] for s in run.spans]
    batches = names.count("maint.propagate")
    if not batches or "maint.apply_edges" not in names:
        return None
    rows = sum(s["attrs"]["capacity"] for s in run.spans
               if s["name"] == "store.merge_device")
    return float(rows) / batches
