"""Stable device-side names: each Pallas kernel's ``name=`` and the
``jax.named_scope`` of each stage of the streaming update, as they appear in
the lowered program (and so in a device trace's op names and metadata)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import streaming
from repro.core.error_engine import probe_omega
from repro.kernels import hadamard, sampled_dot, sketch_fused

F32 = jnp.float32


def _sketch_fused():
    return jax.jit(lambda P, A: sketch_fused.sketch_fused(
        P, A, bn=128, bd=128, interpret=True)).lower(
        jnp.ones((8, 128), F32), jnp.ones((128, 128), F32))


def _sampled_dot():
    return jax.jit(lambda As, Bs, na, nb, r, c: sampled_dot.sampled_rescaled_dot(
        As, Bs, na, nb, r, c, interpret=True)).lower(
        jnp.ones((16, 8), F32), jnp.ones((16, 8), F32), jnp.ones(16, F32),
        jnp.ones(16, F32), jnp.zeros(32, jnp.int32), jnp.zeros(32, jnp.int32))


def _blocked_fwht():
    return jax.jit(lambda X, s: hadamard.blocked_fwht(
        X, s, b=8, bn=128, interpret=True)).lower(
        jnp.ones((64, 128), F32), jnp.ones(64, F32))


def _chunk_contribution():
    key = jax.random.PRNGKey(0)
    return streaming._chunk_contribution.lower(
        key, None, None, jnp.ones((32, 6), F32), jnp.ones((32, 4), F32),
        jnp.arange(32, dtype=jnp.int32), k=8, method="gaussian",
        precision=None)


def _probe_chunk():
    omega = probe_omega(jax.random.PRNGKey(0), 4, 3)
    return streaming._probe_chunk.lower(
        omega, jnp.ones((32, 6), F32), jnp.ones((32, 4), F32),
        precision=None)


def _sparse_contribution(shared):
    from repro.core.types import SparseRows
    X = SparseRows(jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.int32),
                   jnp.ones(64, F32), (32, 6))
    return lambda: streaming._sparse_contribution.lower(
        jax.random.PRNGKey(0), probe_omega(jax.random.PRNGKey(0), 6, 3),
        X, None if shared else X, jnp.arange(32, dtype=jnp.int32), k=8)


SPARSE_SCOPES = ["/sparse_sketch/", "/sparse_norms/", "/sparse_probe/",
                 "sparse_rows/pallas_call"]


@pytest.mark.parametrize("lower,scopes", [
    (_sketch_fused, ["sketch_fused/pallas_call"]),
    (_sampled_dot, ["sampled_dot/pallas_call"]),
    (_blocked_fwht, ["fwht_stage1/pallas_call", "fwht_stage2/pallas_call"]),
    (_chunk_contribution, ["/sketch/", "/norms/"]),
    (_probe_chunk, ["/probe/"]),
    (_sparse_contribution(shared=False), SPARSE_SCOPES),
    (_sparse_contribution(shared=True), SPARSE_SCOPES),
], ids=["sketch_fused", "sampled_dot", "blocked_fwht", "chunk_contribution",
        "probe_chunk", "sparse_contribution", "sparse_contribution_shared"])
def test_lowered_program_carries_each_name(lower, scopes):
    text = lower().as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
