"""Mean `quotient.query_wave` span (the program's own, `repro.obs`) in
the traced window."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "quotient.query_wave"]
    return 1e-6 * sum(durs) / len(durs) if durs else None
