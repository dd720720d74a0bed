"""Seconds of ``DistributedHierarchy.setup`` (partition, Section-5 plan
selection, ELL layout, placement), by the benchmark's clock around the
call; no compile happens in it."""


def read(run):
    return run.device_setup_s
