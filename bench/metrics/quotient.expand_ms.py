"""Host time of the `quotient.expand` spans (each wave's block masks
expanded to node ids) per query call in the traced window."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "quotient.expand"]
    calls = run.work.get("calls")
    return 1e-6 * sum(durs) / calls if durs and calls else None
