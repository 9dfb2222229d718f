"""Roofline machinery unit tests: HLO analyzer (trip counts, dot flops,
fusion io), collective parser, sharding rules, shapes/applicability."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.shapes import SHAPES, cell_applicable
from repro.roofline import analysis as roof
from repro.roofline import hlo_analyzer as ha


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_analyzer_scan_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x, x).compile()
    cost = ha.analyze(c.as_text())
    assert abs(cost.flops - 2 * 128 ** 3 * 10) / (2 * 128 ** 3 * 10) < 0.01


def test_analyzer_nested_scans_multiply():
    def f(x, w):
        def outer(c, _):
            def inner(cc, _):
                return cc @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(x, x).compile()
    cost = ha.analyze(c.as_text())
    want = 2 * 64 ** 3 * 12
    assert abs(cost.flops - want) / want < 0.01


def test_analyzer_matches_xla_on_loop_free():
    def g(a, b):
        return jnp.tanh(a @ b) @ b
    x = jax.ShapeDtypeStruct((96, 96), jnp.float32)
    c = jax.jit(g).lower(x, x).compile()
    cost = ha.analyze(c.as_text())
    xla = c.cost_analysis()["flops"]
    assert abs(cost.flops - xla) / xla < 0.05


def test_analyzer_rectangular_dot_contract_dims():
    def f(a, b):
        return jnp.einsum("ij,kj->ik", a, b)   # contract dim 1 of both
    a = jax.ShapeDtypeStruct((32, 100), jnp.float32)
    b = jax.ShapeDtypeStruct((48, 100), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    cost = ha.analyze(c.as_text())
    want = 2 * 32 * 48 * 100
    assert abs(cost.flops - want) / want < 0.05


def test_collective_parser_on_synthetic_hlo():
    hlo = """
HloModule m

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups={}
  %ag = f32[32,16]{1,0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[16,16]{1,0} slice(%ag), slice={[0:16], [0:16]}
}
"""
    stats = roof.collective_bytes(hlo)
    assert stats.by_op["all-reduce"] == 16 * 16 * 4
    assert stats.by_op["all-gather"] == 32 * 16 * 4
    assert stats.count == 2


def test_roofline_terms_and_bottleneck():
    rl = roof.Roofline(flops=197e12, bytes_accessed=819e9 * 2,
                       coll_bytes=50e9 * 0.5,
                       model_flops_per_device=197e12 / 2, chips=256)
    assert abs(rl.t_compute - 1.0) < 1e-9
    assert abs(rl.t_memory - 2.0) < 1e-9
    assert abs(rl.t_collective - 0.5) < 1e-9
    assert rl.bottleneck == "memory"
    assert abs(rl.roofline_fraction - 0.25) < 1e-9


# ---------------------------------------------------------------------------
# shapes / applicability / sharding rules
# ---------------------------------------------------------------------------

def test_cell_applicability_long_context():
    ok, _ = cell_applicable("hybrid", "long_500k")
    assert ok
    ok, reason = cell_applicable("dense", "long_500k")
    assert not ok and "quadratic" in reason


def test_shapes_registry_complete():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert SHAPES["train_4k"].kind == "train"
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].global_batch == 1


@pytest.mark.dist
def test_sharding_divisibility_fallback():
    """12 heads / 16-way model axis -> replicate (whisper case)."""
    from tests.dist.helpers import run_with_devices
    out = run_with_devices("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shr
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    # divisible: sharded; non-divisible: replicated on that dim
    spec = shr.param_spec(mesh, "/mlp/up/w", (64, 128))
    assert spec == P(("data",), "model"), spec
    spec = shr.param_spec(mesh, "/mlp/up/w", (64, 126))   # 126 % 4 != 0
    assert spec == P(("data",), None), spec
    spec = shr.param_spec(mesh, "/embed/table", (512, 64))
    assert spec == P("model", ("data",)), spec
    spec = shr.param_spec(mesh, "/groups/0/0/attn/wo/w", (5, 64, 64))
    assert spec[0] is None, spec   # stacked leading dim never sharded
    print("SHARDING_OK")
    """)
    assert "SHARDING_OK" in out


@pytest.mark.dist
def test_cache_spec_kv_fallbacks():
    from tests.dist.helpers import run_with_devices
    out = run_with_devices("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shr
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    # kv=4 divides model axis 4 -> shard kv
    spec = shr.cache_spec(mesh, "/k", (8, 16, 128, 4, 64))
    assert spec == P(None, ("data",), None, "model", None), spec
    # kv=2 does not divide -> fall through to head_dim
    spec = shr.cache_spec(mesh, "/k", (8, 16, 128, 2, 64))
    assert spec == P(None, ("data",), None, None, "model"), spec
    print("CACHE_OK")
    """)
    assert "CACHE_OK" in out


@pytest.mark.dist
def test_mesh_factories():
    from tests.dist.helpers import run_with_devices
    out = run_with_devices("""
    from repro.launch.mesh import make_production_mesh, dp_axes
    m1 = make_production_mesh()
    assert dict(m1.shape) == {"data": 16, "model": 16}
    m2 = make_production_mesh(multi_pod=True)
    assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
    assert dp_axes(m2) == ("pod", "data")
    print("MESH_OK")
    """, n_devices=512)
    assert "MESH_OK" in out
