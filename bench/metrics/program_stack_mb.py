"""Megabytes a window's program groups copy through their vmap lanes: the
``bytes`` stat of the program's ``dx.program.run`` spans (every region not
shared, stacked in, and each written one taken back out) over its
``dx.flush`` spans. A program without that span reports nothing."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    n = program_trace.windows(prog)
    span = (prog or {}).get("spans", {}).get("dx.program.run")
    if not n or span is None:
        return None
    return span["bytes"] / n / 1e6
