"""Mean host time per flush window spent in the program's reads from the
device: its ``dx.sync.*`` spans (each a host read that waits for the
device) over its ``dx.flush`` spans."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    n = program_trace.windows(prog)
    if not n:
        return None
    return 1e3 * sum(v["total_s"] for k, v in prog["spans"].items()
                     if k.startswith("dx.sync.")) / n
