"""Mean host time of running a window's access programs: the program's
``dx.program.run`` spans (each program group's executable call, vmapped
or one by one) over its ``dx.flush`` spans. A program without that span
reports nothing."""
import program_trace


def read(run):
    prog = program_trace.of(run)
    n = program_trace.windows(prog)
    span = (prog or {}).get("spans", {}).get("dx.program.run")
    if not n or span is None:
        return None
    return 1e3 * span["total_s"] / n
