"""Process start to window open: imports, weights, compile, histories
and the population's ramp."""


def read(run):
    return run.setup.get("total")
