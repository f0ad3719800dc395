"""Device time of the fused build program per build, from the trace."""


def read(run):
    if run.trace is None or not run.work.get("builds"):
        return None
    s = run.trace.program_seconds(r"_fused_build")
    return None if s is None else 1000.0 * s / run.work["builds"]
