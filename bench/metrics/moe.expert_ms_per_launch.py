"""Device time of the ``moe_experts`` kernel per fused launch in the
traced slice, in ms: every layer's held-expert FFN of one step. None
where the trace holds no such kernel."""
KERNEL = "moe_experts"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    dev_s = tr.op_time(KERNEL)
    ls = run.traced_launches()
    if dev_s <= 0 or not ls:
        return None
    return 1e3 * dev_s / len(ls)
