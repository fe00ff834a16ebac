"""Model-step time per thousand real prompt tokens: the sum of the
latencies RealEngine.execute measured (to block_until_ready, padding
included), over the real tokens of those steps."""


def read(run):
    steps = run.executes()
    tokens = sum(e[3] for e in steps) * run.prompt_len
    if not tokens:
        return None
    return 1000.0 * sum(e[5] for e in steps) / (tokens / 1000.0)
