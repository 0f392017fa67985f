"""Of the reply tokens the client received for turns sent in the window,
the share it had not yet played when the user barged in (counted by the
client)."""


def read(run):
    turns = run.attempted()
    got = sum(len(t.tokens) for t in turns)
    if not got:
        return None
    return sum(t.wasted for t in turns) / got
