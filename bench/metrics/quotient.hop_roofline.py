"""The query hop's share of its HBM roofline: the least time for the
hops' bytes (`bytecount.hop_bytes`, summed over the window's waves) at
the chip's HBM peak, over the hop program's device time."""


def read(run):
    if run.trace is None or not run.work.get("hbm_bytes"):
        return None
    s = run.trace.program_seconds(r"_hop\b")
    if not s:
        return None
    return 100.0 * run.work["hbm_bytes"] / run.peaks["hbm_bytes_per_s"] / s
