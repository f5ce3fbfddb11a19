"""Share of the HBM roofline: the least bytes the window's accesses need
(``roofline.needed_bytes``) at the chip's peak bandwidth, over the device's
busy time in the trace."""


def read(run):
    if not run.trace or run.trace["busy_s"] <= 0:
        return None
    need = run.needed_bytes()
    if not need:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / run.trace["busy_s"]
