"""Share of the chip's busy time in the traced window that the Pallas
selective-scan kernel's events take (their self time). Nothing to read where
no ``mamba_scan`` ran."""
KERNEL = "mamba_scan"           # the kernel's name in the trace


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    kernel_s = sum(s for name, (_, s) in run.trace.ops.items()
                   if name == KERNEL or name.startswith(KERNEL + "."))
    if kernel_s <= 0:
        return None
    return 100.0 * kernel_s / run.trace.busy_s
