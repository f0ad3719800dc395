"""Host time blocked in `build.sync` spans per build in the traced
window: the fused build's one history fetch, which waits for the build
program to finish and then moves the history."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "build.sync"]
    builds = run.work.get("builds")
    return 1e-6 * sum(durs) / builds if durs and builds else None
