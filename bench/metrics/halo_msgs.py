"""Messages of the neighbourhood exchange per V-cycle step: each
operator's plan messages times its applications in a step (``bench.work``);
nothing where no level exchanges."""

from bench import work


def read(run):
    return work.halo_msgs(run.levels, run.solver)
