"""Record operations of the window's requests (each key read, each record
updated, each key counted) over the seconds from the window's start until
the last of them completed."""


def read(run):
    if not run.ops or run.window_s <= 0:
        return None
    return run.ops / run.window_s
