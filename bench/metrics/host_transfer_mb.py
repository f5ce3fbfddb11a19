"""Megabytes moved between host and device per flush window: the ``bytes``
stat of the program's ``dx.sync.*`` (device to host) and ``dx.h2d.*``
(host to device) spans over its ``dx.flush`` spans."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    n = program_trace.windows(prog)
    if not n:
        return None
    moved = sum(v["bytes"] for k, v in prog["spans"].items()
                if k.startswith(("dx.sync.", "dx.h2d.")))
    return moved / n / 1e6
