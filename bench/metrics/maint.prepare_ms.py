"""Host time of the `maint.prepare` spans (a frontier batch deduplicated
and padded on the host before its upload) per update batch, a batch
being one `maint.propagate` span; 0 for batches that prepared nothing.
Read only where the program names its edge rewrites
(`maint.apply_edges`), as a program that has these spans does."""


def read(run):
    names = [s["name"] for s in run.spans]
    batches = names.count("maint.propagate")
    if not batches or "maint.apply_edges" not in names:
        return None
    durs = [s["dur"] for s in run.spans if s["name"] == "maint.prepare"]
    return 1e-6 * sum(durs) / batches
