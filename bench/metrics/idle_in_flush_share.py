"""Share of the traced window in which the first device was idle while the
host was inside the program's ``dx.flush`` (lowering and dispatching a
window)."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    if not program_trace.windows(prog) or prog["window_s"] <= 0:
        return None
    return 100.0 * prog["spans"]["dx.flush"]["idle_s"] / prog["window_s"]
