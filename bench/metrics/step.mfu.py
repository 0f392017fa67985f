"""Model FLOPs of the valid tokens launched in the traced slice (the
configuration's architecture file, ``step_flops``) over the device time
of the fused step's executions there (the programs that run inside the
``bench.launch`` spans) times the chip's peak bf16 FLOP/s, in percent."""


def read(run):
    tr = run.trace
    if tr is None or not run.peak:
        return None
    dev_s = tr.module_time_in("bench.launch")
    ls = run.traced_launches()
    if dev_s <= 0 or not ls:
        return None
    flops = sum(run.cell.arch.step_flops(run.conf, x.rows) for x in ls)
    return 100.0 * flops / (dev_s * run.peak["bf16_flops_per_s"])
