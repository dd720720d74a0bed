"""Compiles for a described TPU v5e chip: what the chip's compiler accepts.

Nothing runs: the TPU compiler that JAX ships compiles for a chip that is
described (``v5e:2x2``), not attached, and raises what the chip's compiler
would raise.  The topology is described inside a fixture, never while this
module is imported, so every test worker collects the same tests and only
the one given this file loads the TPU library.

Each op of ``repro.kernels.IMPLS`` is compiled at a real width with the
table's TPU row in force: a Pallas kernel shows up in the compiled program
as a ``tpu_custom_call``, an XLA body does not.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro import kernels
from repro.kernels.flash_attention.ops import attention
from repro.kernels.moe_pack.ops import pack
from repro.kernels.spmv_ell.ops import spmv
from repro.kernels.ssd_scan.ops import ssd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch table's TPU row, in a process that runs on the CPU."""
    monkeypatch.setattr(kernels, "platform", lambda: "tpu")


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def examples(one_chip):
    """op -> {case: (fn, argument shapes)} at the widths the models and
    the solver run."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    n = 2 ** 20
    return {
        # mixtral-8x7b: 32 query heads, 8 kv heads, head dim 128
        "flash_attention": {
            "prefill_4k": (
                lambda q, k, v: attention(q, k, v, causal=True),
                (S((1, 32, 4096, 128), bf), S((1, 8, 4096, 128), bf),
                 S((1, 8, 4096, 128), bf)),
            ),
            "decode_32k": (
                lambda q, k, v, t: attention(q, k, v, causal=True,
                                             kv_len=t, q_offset=t - 1),
                (S((1, 32, 1, 128), bf), S((1, 8, 32768, 128), bf),
                 S((1, 8, 32768, 128), bf), S((), i32)),
            ),
        },
        # the AMG fine level: 2^20 rows of the 7-point stencil, f64 values
        "spmv_ell": {
            "fine_level_f64": (
                spmv,
                (S((n, 7), i32), S((n, 7), jnp.float64),
                 S((n + 1,), jnp.float64)),
            ),
        },
        # mixtral-8x7b top-2 dispatch of 4096 tokens at d_model 4096
        "moe_pack": {
            "pack_top2": (pack, (S((4096, 4096), bf), S((8192,), i32))),
        },
        # mamba2-780m: 48 heads of 64, state 128, one group
        "ssd_scan": {
            "prefill_4k": (
                ssd,
                (S((1, 4096, 48, 64), f32), S((1, 4096, 48), f32),
                 S((48,), f32), S((1, 4096, 1, 128), f32),
                 S((1, 4096, 1, 128), f32)),
            ),
        },
    }


@pytest.mark.parametrize("op", sorted(kernels.IMPLS))
def test_op_compiles_as_routed(one_chip, on_tpu, op):
    """Every op compiles for the chip through the implementation the table
    names for ``tpu``, and that implementation is what the program holds."""
    want_kernel = kernels.IMPLS[op]["tpu"] == "pallas"
    assert kernels.impl(op) == kernels.IMPLS[op]["tpu"]
    for case, (fn, args) in examples(one_chip)[op].items():
        # the solver's f64 needs x64; the models' bf16 kernels must not
        # see it (Mosaic cannot truncate f64)
        with jax.enable_x64(any(a.dtype == jnp.float64 for a in args)):
            text = compiled_text(fn, *args)
        assert ("tpu_custom_call" in text) == want_kernel, (op, case)


def test_every_pallas_op_compiles(one_chip, on_tpu):
    """The ops the table runs as Pallas on a TPU are exactly the ones whose
    kernels the chip's compiler accepts, at every width listed."""
    routed = [op for op, row in kernels.IMPLS.items() if row["tpu"] == "pallas"]
    assert routed
    cases = examples(one_chip)
    for op in routed:
        for case, (fn, args) in cases[op].items():
            assert "tpu_custom_call" in compiled_text(fn, *args), (op, case)


def test_coarse_vcycle_step_compiles(topo, on_tpu, monkeypatch):
    """One V-cycle step of a small DistributedHierarchy, compiled for one
    chip: the whole device program of the solve (halo executors, SpMVs,
    Chebyshev smoothers, coarse solve), as the chip receives it."""
    from repro.amg import DistributedHierarchy, build_hierarchy, diffusion_2d
    from repro.core import PlanCache

    mesh = Mesh(np.array(topo.devices[:1]), ("proc",))
    # nothing can be placed on a described chip: the operands stay on the
    # host and enter the compile as shapes
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: np.asarray(x))
    with jax.enable_x64(True):
        dh = DistributedHierarchy.setup(
            build_hierarchy(diffusion_2d(16, 16)), mesh, cache=PlanCache()
        )
        step, consts = dh.step_program()
        vec = jax.ShapeDtypeStruct((1, dh.levels[0].pad), jnp.float64,
                                   sharding=NamedSharding(mesh, P("proc")))
        shapes = [
            jax.ShapeDtypeStruct(np.shape(c), np.asarray(c).dtype,
                                 sharding=NamedSharding(mesh, P()))
            for c in consts
        ]
        compiled = step.lower(shapes, vec, vec).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0
