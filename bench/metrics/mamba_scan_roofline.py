"""The Pallas selective-scan kernel's share of its bytes roofline: the least
time its calls in the traced window could take at the HBM peak (the bytes of
``bench/lib/flops_hybrid.mamba_scan_bytes`` at each step's padded batch, one
call per Mamba layer) over the summed device self time of its events. The
scan does no matrix product, so the MXU's peak is no roof for it. Nothing to
read where no ``mamba_scan`` ran."""
from bench.lib.flops_hybrid import mamba_layers, mamba_scan_bytes

KERNEL = "mamba_scan"           # the kernel's name in the trace


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
    nbytes = mamba_layers(run.dims) * sum(
        mamba_scan_bytes(run.dims, e[4], run.prompt_len) for e in steps)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / kernel_s
