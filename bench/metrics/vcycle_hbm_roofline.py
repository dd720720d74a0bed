"""Share of the HBM roofline a V-cycle step reaches: the least time the
chips could take to move the bytes a step must move (``bench.work``), at
the chip's HBM rate (``bench.peaks``), over the device time per step.  The
step does no matrix products, so bytes bound it."""

from bench import work
from bench.peaks import peaks


def read(run):
    if run.trace is None or run.n_vcycles == 0:
        return None
    least_s = work.vcycle_bytes(run.levels, run.solver, run.value_bytes) / (
        run.n_chips * peaks(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s * run.n_vcycles / run.trace["busy_s"]
