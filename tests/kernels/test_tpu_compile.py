"""Compile the main-path Pallas kernels for a TPU v5e, at real widths.

Interpret mode runs the kernel bodies on the CPU, but it cannot see what
Mosaic refuses: blocks not aligned to the (8, 128) tiling, or more VMEM
than a kernel may use. These tests compile each kernel for a described
(not attached) ``v5e:2x2`` chip and check that the result holds the
Mosaic kernel (``tpu_custom_call``) under the kernel's stable name, the
one the device trace shows. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every worker imports
every test file. Block sizes come from ``tuning.lookup``, as on the
program's default path. One more test reads the compiled ingest update
(``_chunk_contribution``) for where the chunk's projection is generated.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import streaming
from repro.kernels import (
    hadamard, sampled_dot, sketch_fused, sparse_rows, tuning)

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


def _assert_mosaic(compiled, *names):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert f"%{name}." in text, name


@pytest.mark.parametrize("precision", [None, "bf16"])
def test_sketch_fused_compiles_for_v5e(one_chip, precision):
    bn, bd = tuning.lookup("sketch_fused", (K, D_CHUNK, N)).block
    compiled = _compile(
        lambda Pi, A: sketch_fused.sketch_fused(
            Pi, A, bn=bn, bd=bd, interpret=False, precision=precision),
        one_chip, ((K, D_CHUNK), jnp.float32), ((D_CHUNK, N), jnp.float32))
    _assert_mosaic(compiled, "sketch_fused")


def test_sampled_dot_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda As, Bs, na, nb, rows, cols: sampled_dot.sampled_rescaled_dot(
            As, Bs, na, nb, rows, cols, interpret=False),
        one_chip, ((N, K), jnp.float32), ((N, K), jnp.float32),
        ((N,), jnp.float32), ((N,), jnp.float32),
        ((M,), jnp.int32), ((M,), jnp.int32))
    _assert_mosaic(compiled, "sampled_dot")


def test_blocked_fwht_compiles_for_v5e(one_chip):
    cfg = tuning.lookup("blocked_fwht", (D_CHUNK, N))
    assert tuning.vmem_bytes(cfg, (D_CHUNK, N)) <= tuning.VMEM_BUDGET_BYTES
    b, bn = cfg.block
    compiled = _compile(
        lambda X, signs: hadamard.blocked_fwht(
            X, signs, b=b, bn=bn, grid_order=cfg.grid_order,
            interpret=False),
        one_chip, ((D_CHUNK, N), jnp.float32), ((D_CHUNK,), jnp.float32))
    _assert_mosaic(compiled, "fwht_stage1", "fwht_stage2")


def _computations(text):
    """{name: body} of each computation in a compiled module's text."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}$", text, re.M | re.S)}


def _called(comps, name):
    """The computation ``name`` and every one it calls, transitively."""
    seen, todo = set(), [name]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += re.findall(r"calls=%([\w.\-]+)", comps[c])
    return seen


@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_chunk_projection_generated_once_for_v5e(one_chip, method):
    """One chunk's projection block is one fusion that both sketch dots
    read, not a producer regenerated inside each dot's tiles."""
    plan = ((((2 ** 20,), jnp.float32), ((K,), jnp.int32))
            if method == "srht" else ())
    compiled = _compile(
        lambda key, A, B, gids, *sr: streaming._chunk_contribution(
            key, *(sr or (None, None)), A, B, gids, k=K, method=method,
            precision=None),
        one_chip, ((2,), jnp.uint32), ((D_CHUNK, N), jnp.float32),
        ((D_CHUNK, N), jnp.float32), ((D_CHUNK,), jnp.int32), *plan)
    text = compiled.as_text()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    producers = re.findall(rf"= f32\[{D_CHUNK},{K}\]\S* fusion\(",
                           comps[entry])
    assert len(producers) == 1
    generation = " xor(" if method == "gaussian" else " popcnt("
    dots = [c for c in re.findall(r"calls=%([\w.\-]+)", comps[entry])
            if any(" convolution(" in comps[f] for f in _called(comps, c))]
    assert len(dots) == 2
    for dot in dots:
        assert not any(generation in comps[f] for f in _called(comps, dot))


W, T_DOCS, CAP = 102660, 15000, 3604480   # the nytbow.ingest cell's chunk


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared", "distinct"])
def test_sparse_update_compiles_for_v5e_without_a_gather_of_every_nonzero(
        one_chip, monkeypatch, shared):
    """A sparse chunk's sketches, norms and probe summand at the
    bag-of-words cell's widths: they compile, through the ``sparse_rows``
    kernel, and their temporaries stay far under one (nonzeros, k) f32
    gather (7.4 GB). One chunk fed as both operands compiles to one column
    sort and two kernel passes (``B omega`` by row, A by column); a
    distinct pair to two column sorts and three passes. (The row pass
    holds a sort too, which it skips at run time: the entries come in row
    order.)"""
    from repro.core.types import SparseRows
    from repro.kernels import ops
    # the platform here is the CPU, which would take the interpreter
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    streaming._sparse_contribution.clear_cache()

    def update(key, omega, gids, *leaves):
        A, B = (SparseRows(*leaves[i:i + 3], (T_DOCS, W))
                for i in (0, len(leaves) - 3))
        return streaming._sparse_contribution(
            key, omega, A, None if shared else B, gids, k=K)

    chunk = (((CAP,), jnp.int32), ((CAP,), jnp.int32), ((CAP,), jnp.float32))
    try:
        compiled = _compile(
            update, one_chip, ((2,), jnp.uint32), ((W, 16), jnp.float32),
            ((T_DOCS,), jnp.int32), *chunk * (1 if shared else 2))
    finally:    # keep the compiled-for-TPU traces out of later calls
        streaming._sparse_contribution.clear_cache()
        sparse_rows.rows_accumulate.clear_cache()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
    _assert_mosaic(compiled, "sparse_rows")
    text = compiled.as_text()
    assert f"f32[{CAP},{K}]" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (2 if shared else 3)
    entry_sorts = [line for line in text.splitlines()
                   if " sort(" in line and f"[{CAP}]" in line]
    assert sum("/sparse_sketch/" in line for line in entry_sorts) == \
        (1 if shared else 2)
