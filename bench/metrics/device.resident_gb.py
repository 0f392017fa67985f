"""The most the chip held between two rounds of the window, in GB (1e9):
``bytes_in_use`` read after each round of the traced run, once the
step's result has replaced the page store it was given. The serving
state (weights, page store, buffers), not the set-up's warm-up peak."""


def read(run):
    got = [b for t, b in run.resident if run.in_window(t)]
    return max(got) / 1e9 if got else None
