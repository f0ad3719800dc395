"""Mean `maint.propagate` span (the program's own, `repro.obs`) per
update batch in the traced window."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "maint.propagate"]
    return 1e-6 * sum(durs) / len(durs) if durs else None
