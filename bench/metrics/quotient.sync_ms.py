"""Host time blocked in `quotient.sync` spans (each wave's block-mask
fetch, which waits for the wave's hops) per query call in the traced
window."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "quotient.sync"]
    calls = run.work.get("calls")
    return 1e-6 * sum(durs) / calls if durs and calls else None
