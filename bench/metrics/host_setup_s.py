"""Seconds of ``build_hierarchy`` (coarsening, interpolation, Galerkin
products on the host), by the benchmark's clock around the call."""


def read(run):
    return run.host_setup_s
