"""The fused build's share of its HBM roofline: the least time the chip
could take for the algorithm's bytes (`bytecount.build_bytes`) at the
chip's HBM peak, over the build program's device time."""


def read(run):
    if run.trace is None or not run.work.get("hbm_bytes"):
        return None
    s = run.trace.program_seconds(r"_fused_build")
    if not s:
        return None
    return 100.0 * run.work["hbm_bytes"] / run.peaks["hbm_bytes_per_s"] / s
