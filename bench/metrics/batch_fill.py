"""Real requests per model step, as a share of the executor's max batch for
the step's expert (CoServe's profiled max batch, capped by batch memory)."""


def read(run):
    steps = run.executes()
    if not steps:
        return None
    return 100.0 * sum(e[3] / e[6] for e in steps) / len(steps)
