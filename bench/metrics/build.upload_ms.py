"""Host time of the `build.upload` spans (the graph's four columns to
the device) per build in the traced window."""


def read(run):
    durs = [s["dur"] for s in run.spans if s["name"] == "build.upload"]
    builds = run.work.get("builds")
    return 1e-6 * sum(durs) / builds if durs and builds else None
