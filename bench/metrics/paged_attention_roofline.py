"""The paged attention kernel's share of its roofline, in percent: the
least time the chip needs for the FLOPs and bytes the valid query
tokens require (``attention_cost`` of ``bench/arch/common.py``; memory
bound at these sizes) over the kernel's device time in the traced
slice. Grid steps past a row's length count as time, not as work, so
they lower it."""
from bench.arch.common import attention_cost, least_time

KERNEL = "paged_attention"


def read(run):
    tr = run.trace
    if tr is None or not run.peak:
        return None
    dev_s = tr.op_time(KERNEL)
    ls = run.traced_launches()
    if dev_s <= 0 or not ls:
        return None
    need = 0.0
    for x in ls:
        f, b = attention_cost(run.conf, x.rows)
        need += least_time(f, b, run.peak)[0]
    return 100.0 * need / dev_s
