"""Compile every registered Pallas kernel for a TPU v5e chip, at real widths.

Interpret-mode parity (tests/test_kernels.py) cannot see what the chip's
compiler refuses: blocks whose last two dims break the (8, 128) tiling
rule, or more fast memory than a kernel may use. These tests hand each
``KERNEL_REGISTRY`` kernel's ``pallas_fn`` to the TPU compiler for one
chip of a described ``v5e:2x2`` topology (no chip attached) and assert
that the kernel survives as a ``tpu_custom_call``. The benchmark's
count of ``ensemble_score``'s work, read from that custom call's
operand shapes, is checked on the compiled jit wrapper too.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers each import every test file. The fixture skips where
no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import KERNEL_REGISTRY

F32, I8 = jnp.float32, jnp.int8

# name -> (argument shapes and dtypes, static keyword arguments)
SHAPES = {
    "rbf_gram": ([((1024, 64), F32), ((1024, 64), F32)], {"gamma": 0.5}),
    "gram_matvec": ([((10_000, 64), F32), ((10_000, 64), F32), ((10_000,), F32)],
                    {"gamma": 0.5}),
    "rbf_gram_q8": ([((1024, 64), F32), ((1024, 64), I8), ((64,), F32), ((64,), F32)],
                    {"gamma": 0.5}),
    "batched_rbf_gram": ([((64, 512, 64), F32), ((64, 512, 64), F32), ((64,), F32)], {}),
    "flash_attention": ([((4, 1024, 8, 128), F32)] * 3, {}),
    "ensemble_score": ([((256, 64), F32), ((40, 128, 64), F32), ((40, 128), F32),
                        ((40, 128), F32)], {"k": 50}),
    "ensemble_score_q8": ([((256, 64), F32), ((32, 512, 64), I8), ((32, 64), F32),
                           ((32, 64), F32), ((32, 512), F32), ((32,), F32)], {}),
}


def test_every_registered_kernel_has_a_shape():
    assert set(SHAPES) == set(KERNEL_REGISTRY)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e topology, with the persistent compile
    cache off: a program compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_kernel_compiles_for_v5e(one_chip, name):
    shapes, static = SHAPES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    fn = KERNEL_REGISTRY[name].pallas_fn
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ensemble_score_call_counts_its_row_tiles(one_chip):
    """The round's roofline reader (``bench.counting.cost_of``) counts an
    ``ensemble_score`` call from the operand shapes of the jitted
    wrapper's custom call: (b, d) queries, then (T, bn, d) support
    tiles. Compile that wrapper for the chip and count its HLO: the
    count must be the 2 b T bn (d + 1) the kernel does."""
    from jax._src.lib import _jax

    from bench.counting import cost_of
    from repro.kernels import ops

    b, tiles, bn, d = 256, 40, 128, 784
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((b, d), (tiles, bn, d), (tiles, bn), (tiles, bn))]
    compiled = ops._ens_tpu.lower(*args, k=50).compile()
    options = _jax.HloPrintOptions()
    options.print_operand_shape = True
    lines = [line.strip()
             for module in compiled.runtime_executable().hlo_modules()
             for line in module.to_string(options).splitlines()]
    counts = [c for c in (cost_of("ensemble_score", line) for line in lines) if c]
    assert len(counts) == 1
    assert counts[0][0] == 2 * b * tiles * bn * (d + 1)
