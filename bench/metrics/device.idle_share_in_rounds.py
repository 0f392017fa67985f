"""1 - device busy time over the union of the traced slice's
``bench.round`` host spans: the device's idle share while a round's work
was ready (users pacing the system do not count)."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = tr.busy_share_in("bench.round")
    return None if busy is None else 1.0 - busy
