"""The Pallas flash-attention kernel's share of its roofline: the least time
its calls in the traced window could take (the larger of operations over the
bf16 peak and bytes over the HBM peak, from the benchmark's shape functions
at each step's padded batch) over the summed device time of its events."""
from bench.lib.flops import flash_attention_cost

KERNEL = "flash_attention"      # the jitted kernel's name in the trace


def _is_kernel(name):
    return name == KERNEL or name.startswith(KERNEL + ".")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = sum(s for name, (_, s) in run.trace.ops.items()
                   if _is_kernel(name))
    steps = run.executes()
    if kernel_s <= 0 or not steps:
        return None
    least = 0.0
    for e in steps:
        flops, nbytes = flash_attention_cost(run.dims, e[4], run.prompt_len)
        least += run.dims.layers * max(flops / run.peaks["bf16_flops"],
                                       nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
