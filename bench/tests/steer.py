"""Drive the harness on the CPU at a small grid, with its look for a chip
steered off and, if asked, a fault planted in the program underneath.

    python3 bench/tests/steer.py [--ny 32 --nx 32] [--fault F] \\
        (run|calibrate) <arguments of bench/run.py or bench/calibrate.py>

Faults, each one a solve can have:

- ``unchanged``: the step returns its iterate unchanged;
- ``half``: the step updates only the first half of each row block;
- ``exchange``: the halo exchange between chips is left out (ghost values
  read as zeros);
- ``altered``: ``solve`` alters one entry of the answer it returns.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from repro.amg.distributed import DistributedHierarchy as DH
    from repro.sparse.device import make_distributed_spmv

    make_step, bind, solve = DH._make_step, DH._bind, DH.solve

    if fault == "unchanged":
        def _make_step(self):
            def step(x, b):
                return x, jnp.linalg.norm(b - self._Amv[0](x))
            return step
        DH._make_step = _make_step
    elif fault == "half":
        def _make_step(self):
            inner = make_step(self)

            def step(x, b):
                x_new, rn = inner(x, b)
                keep = jnp.arange(x.shape[1]) < x.shape[1] // 2
                return jnp.where(keep, x_new, x), rn
            return step
        DH._make_step = _make_step
    elif fault == "exchange":
        def _bind(self, op):
            if not op.ell.ghost_pad:
                return bind(self, op)
            g = op.ell.ghost_pad

            def no_exchange(v):
                return jnp.zeros((v.shape[0], g) + v.shape[2:], v.dtype)
            return make_distributed_spmv(op.ell, self.mesh, self.axis_name,
                                         no_exchange,
                                         overlap=(op.overlap_mode == "on"))
        DH._bind = _bind
    elif fault == "altered":
        def _solve(self, b, **kw):
            x, hist = solve(self, b, **kw)
            x = x.copy()
            x[len(x) // 2] += 1e-3 * float(abs(x).max())
            return x, hist
        DH.solve = _solve
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ny", type=int, default=32)
    ap.add_argument("--nx", type=int, default=32)
    ap.add_argument("--fault", default="none")
    ap.add_argument("entry", choices=("run", "calibrate"))
    args, rest = ap.parse_known_args(argv)

    import jax

    from bench import calibrate, run

    config = run.load_config
    run.load_config = lambda f: {**config(f), "ny": args.ny, "nx": args.nx}
    run.require_devices = lambda n: jax.devices()[:n]
    plant(args.fault)
    return (run.main if args.entry == "run" else calibrate.main)(rest)


if __name__ == "__main__":
    sys.exit(main())
