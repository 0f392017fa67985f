"""Share of the fused step's [slots, Q bucket] token grid that carried no
valid token, summed over the window's launches."""


def read(run):
    ls = run.window_launches()
    grid = sum(x.B * x.Q for x in ls)
    if not grid:
        return None
    valid = sum(n for x in ls for _, n in x.rows)
    return (grid - valid) / grid
