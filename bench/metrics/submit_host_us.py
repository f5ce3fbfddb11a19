"""Mean host self time of one ``submit_*`` call: the benchmark's ``submit``
span less any ``flush_async`` the call triggered inside it."""


def read(run):
    subs = [(s, e) for name, s, e in run.spans if name == "submit"]
    if not subs:
        return None
    flushes = [(s, e) for name, s, e in run.spans if name == "flush_async"]
    total, j = 0.0, 0
    for s, e in subs:                       # both lists are in time order
        inner = 0.0
        while j < len(flushes) and flushes[j][1] <= e:
            fs, fe = flushes[j]
            if fs >= s:
                inner += fe - fs
            j += 1
        total += (e - s) - inner
    return 1e6 * total / len(subs)
