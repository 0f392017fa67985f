"""Turns finished inside the window (every reply token delivered, or cut
by the user after its first packet) over the window's seconds."""


def read(run):
    n = run.finished_in_window()
    return n / run.seconds if n else None
