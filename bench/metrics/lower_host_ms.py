"""Mean host time of lowering one flush window: the program's
``dx.flush.lower`` spans (plan cache, the six passes, the cost model's
measurements, the hazard scan) over its ``dx.flush`` spans."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    n = program_trace.windows(prog)
    if not n:
        return None
    lower = prog["spans"].get("dx.flush.lower", {}).get("total_s", 0.0)
    return 1e3 * lower / n
