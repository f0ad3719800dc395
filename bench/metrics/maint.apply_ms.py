"""Host time of the `maint.apply_edges` spans (the backend's edge tables
rewritten and re-indexed) per update batch, a batch being one
`maint.propagate` span."""


def read(run):
    batches = sum(s["name"] == "maint.propagate" for s in run.spans)
    durs = [s["dur"] for s in run.spans if s["name"] == "maint.apply_edges"]
    return 1e-6 * sum(durs) / batches if durs and batches else None
