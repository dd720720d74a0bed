"""Device time per V-cycle step: each chip's busy time in the traced window
(the union of its operations), averaged over the chips, over the steps the
window ran."""

from bench.xplane import per_vcycle_ms


def read(run):
    if run.trace is None:
        return None
    return per_vcycle_ms(run.trace["busy_s"], run.n_vcycles)
