"""Mean host time of one ``flush_async`` call (lowering and dispatch of one
window), from the benchmark's span around the service's method."""


def read(run):
    d = [e - s for name, s, e in run.spans if name == "flush_async"]
    return 1e3 * sum(d) / len(d) if d else None
