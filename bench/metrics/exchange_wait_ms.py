"""Device time per V-cycle step in the neighbourhood exchange's
collective-permute operations, averaged over the chips; nothing where no
chip ran one (a one-chip mesh has no exchange)."""

from bench.xplane import per_vcycle_ms


def read(run):
    if run.trace is None:
        return None
    return per_vcycle_ms(run.trace["exchange_s"], run.n_vcycles)
