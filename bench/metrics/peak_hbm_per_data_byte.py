"""Space amplification: peak device bytes in use on the fullest chip over
that chip's share of the data the deployment keeps on the device."""


def read(run):
    if not run.peak_bytes or not run.data_bytes:
        return None
    return run.peak_bytes / (run.data_bytes / run.chips)
