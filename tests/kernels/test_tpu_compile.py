"""Compile the main-path Pallas kernels for a TPU v5e, at real widths.

Interpret mode runs the kernel bodies on the CPU, but it cannot see what
Mosaic refuses: blocks not aligned to the (8, 128) tiling, or more VMEM
than a kernel may use. These tests compile each kernel for a described
(not attached) ``v5e:2x2`` chip and check that the result holds the
Mosaic kernel (``tpu_custom_call``). Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every worker imports
every test file. Block sizes come from ``tuning.lookup``, as on the
program's default path.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hadamard, sampled_dot, sketch_fused, tuning

K, D_CHUNK, N = 512, 16384, 4096      # the chip smoke's ingest widths
M = 65536                             # two sampled_dot launches


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back
    # without one: keep such compiles out of any persistent cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("precision", [None, "bf16"])
def test_sketch_fused_compiles_for_v5e(one_chip, precision):
    bn, bd = tuning.lookup("sketch_fused", (K, D_CHUNK, N)).block
    compiled = _compile(
        lambda Pi, A: sketch_fused.sketch_fused(
            Pi, A, bn=bn, bd=bd, interpret=False, precision=precision),
        one_chip, ((K, D_CHUNK), jnp.float32), ((D_CHUNK, N), jnp.float32))
    _assert_mosaic(compiled)


def test_sampled_dot_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda As, Bs, na, nb, rows, cols: sampled_dot.sampled_rescaled_dot(
            As, Bs, na, nb, rows, cols, interpret=False),
        one_chip, ((N, K), jnp.float32), ((N, K), jnp.float32),
        ((N,), jnp.float32), ((N,), jnp.float32),
        ((M,), jnp.int32), ((M,), jnp.int32))
    _assert_mosaic(compiled)


def test_blocked_fwht_compiles_for_v5e(one_chip):
    cfg = tuning.lookup("blocked_fwht", (D_CHUNK, N))
    assert tuning.vmem_bytes(cfg, (D_CHUNK, N)) <= tuning.VMEM_BUDGET_BYTES
    b, bn = cfg.block
    compiled = _compile(
        lambda X, signs: hadamard.blocked_fwht(
            X, signs, b=b, bn=bn, grid_order=cfg.grid_order,
            interpret=False),
        one_chip, ((D_CHUNK, N), jnp.float32), ((D_CHUNK,), jnp.float32))
    _assert_mosaic(compiled)
