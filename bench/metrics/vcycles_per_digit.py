"""V-cycles the window's solves ran per decimal digit of relative-residual
reduction they made, from the residual histories ``solve`` returned."""


def read(run):
    return run.n_vcycles / run.digits if run.digits > 0 else None
