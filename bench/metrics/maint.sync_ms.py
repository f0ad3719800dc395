"""Host time blocked in `maint.sync` spans (device->host transfers, each
waiting for the device work queued before it) per update batch, a batch
being one `maint.propagate` span.  Read only where the program names
its edge rewrites (`maint.apply_edges`), as a program that has these
spans does."""


def read(run):
    names = [s["name"] for s in run.spans]
    batches = names.count("maint.propagate")
    if not batches or "maint.apply_edges" not in names:
        return None
    durs = [s["dur"] for s in run.spans if s["name"] == "maint.sync"]
    return 1e-6 * sum(durs) / batches
