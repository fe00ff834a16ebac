"""A hybrid model step's share of the chip's bf16 peak over the window: the
operations the real (unpadded) sequences of the window's steps need, from
``bench/lib/flops_hybrid.py`` (matrix work only: the selective scan runs on
the vector unit), over the window's seconds times the peak."""
from bench.lib.flops_hybrid import forward_flops


def read(run):
    steps = run.executes()
    if not steps or run.peaks is None:
        return None
    w = run.window
    flops = sum(e[3] for e in steps) * forward_flops(run.dims,
                                                     run.prompt_len)
    return 100.0 * flops / ((w.t_end - w.t_open) * run.peaks["bf16_flops"])
