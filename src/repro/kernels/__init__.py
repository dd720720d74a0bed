"""Pallas TPU kernels for the compute hot spots, and which body each op runs.

Each kernel lives in ``kernels/<name>/`` with three files:

* ``<name>.py`` — the ``pl.pallas_call`` kernel with explicit BlockSpec VMEM
  tiling (TPU is the target; ``interpret=True`` validates on CPU),
* ``ops.py``   — the jit'd public wrapper (padding, dtype plumbing, vmap),
* ``ref.py``   — the pure-jnp body, compiled by XLA wherever the Pallas
  kernel does not run, and the oracle of the kernel tests.

Which of the two an op runs is set by the platform JAX runs on, from one
table, :data:`IMPLS`.  Nothing falls back at run time: an op routed to
Pallas that the TPU compiler refuses fails to compile.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

#: op -> platform -> implementation: ``"pallas"`` (the Pallas TPU kernel)
#: or ``"xla"`` (the op's jnp body in ``ref.py``, compiled by XLA).
IMPLS = {
    # Mosaic refuses the VMEM gather x[cols, 0] of the SpMV body.
    "spmv_ell": {"tpu": "xla", "cpu": "xla"},
    # Mosaic's gather lowering refuses the row gather of gather_rows and
    # combine_rows ("Shape mismatch in input, indices and output").
    "moe_pack": {"tpu": "xla", "cpu": "xla"},
    # Blocks (1, chunk) and (1, 1) over [H, T] and [H] break the (8, 128)
    # tiling rule.
    "ssd_scan": {"tpu": "xla", "cpu": "xla"},
    # Compiles for v5e at mixtral-8x7b widths, prefill and decode
    # (tests/test_tpu_compile.py).
    "flash_attention": {"tpu": "pallas", "cpu": "xla"},
}

_VALID_OVERRIDES = ("xla", "pallas_interpret")
_override: Optional[str] = None


def platform() -> str:
    """The platform JAX runs this process's computations on."""
    import jax

    return jax.default_backend()


def impl(op: str) -> str:
    """``"xla"``, ``"pallas"`` or ``"pallas_interpret"`` for ``op`` here."""
    if _override is not None:
        return _override
    by_platform = IMPLS[op]
    plat = platform()
    if plat not in by_platform:
        raise RuntimeError(
            f"kernels.IMPLS[{op!r}] names no implementation for platform "
            f"{plat!r}"
        )
    return by_platform[plat]


def impl_table() -> str:
    """:data:`IMPLS` as printable lines, one op per line."""
    plats = sorted({p for row in IMPLS.values() for p in row})
    lines = [f"{'op':16s}" + "".join(f"{p:>8s}" for p in plats)]
    for op, row in IMPLS.items():
        lines.append(f"{op:16s}" + "".join(f"{row[p]:>8s}" for p in plats))
    return "\n".join(lines)


@contextmanager
def use_backend(name: str):
    """Run every op through one implementation inside the block.

    For tests and kernel benchmarks only: ``"pallas_interpret"`` executes
    the Pallas kernel bodies in Python on the CPU, ``"xla"`` the jnp bodies.
    """
    global _override
    if name not in _VALID_OVERRIDES:
        raise ValueError(f"backend {name!r} not in {_VALID_OVERRIDES}")
    old = _override
    _override = name
    try:
        yield
    finally:
        _override = old
