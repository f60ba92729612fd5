"""The HLO cost parser: trip-count multiplication, dot flops, collectives."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_cost


def test_scan_trip_count_multiplied():
    def body(x, _):
        return x @ x, None

    def f(x):
        return jax.lax.scan(body, x, None, length=10)[0]

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    r = hlo_cost.analyze(c.as_text())
    expect = 10 * 2 * 128**3
    assert 0.9 * expect < r["flops_per_device"] < 1.3 * expect
    assert r["unknown_trip_loops"] == 0


def test_dot_flops_exact():
    f = jax.jit(lambda a, b: a @ b)
    c = f.lower(jax.ShapeDtypeStruct((64, 32), jnp.float32),
                jax.ShapeDtypeStruct((32, 16), jnp.float32)).compile()
    r = hlo_cost.analyze(c.as_text())
    assert abs(r["flops_per_device"] - 2 * 64 * 32 * 16) < 2 * 64 * 16  # ±eltwise


def test_shape_parsing():
    assert hlo_cost.shape_bytes("f32[16,4]{1,0}") == 256
    assert hlo_cost.shape_bytes("(bf16[8], s32[2])") == 24
    assert hlo_cost.shape_elems("pred[3,3]") == 9


def test_dus_counted_in_place():
    def f(x, u):
        return jax.lax.dynamic_update_slice(x, u, (0, 0))

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
                         jax.ShapeDtypeStruct((4, 4), jnp.float32)).compile()
    r = hlo_cost.analyze(c.as_text())
    # the dus itself counts as slice traffic; XLA inserts ONE defensive copy
    # of the unaliased input (read+write = 2 buffers). Naive operand+result
    # counting of the dus node alone would give ≥ 2 more buffers on top.
    buf = 1024 * 1024 * 4
    assert r["hbm_bytes_per_device"] < 2.2 * buf


# ---------------------------------------------------------------------------
# cost-model integration (PR 9): the seed parser priced against jaxpr costs
# of known stages — the calibration cross-check docs/profiling.md describes
# ---------------------------------------------------------------------------

from repro.profile.cost import CostEstimate, CostModel, DeviceParams


def _hlo(f, *avals):
    return jax.jit(f).lower(*avals).compile().as_text()


def test_jaxpr_and_hlo_price_agree_on_dot():
    """Static jaxpr pricing and compiled-HLO pricing must agree on the
    dominant term of a matmul — the model the planner consults before
    execution and the parser's post-lowering truth cross-check."""
    m = CostModel()
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    f = lambda x, y: jnp.tanh(x @ y) + 1.0
    est_j = m.price_fn(f, a, b)
    est_h = m.price_hlo(_hlo(f, a, b))
    dot = 2 * 128 * 256 * 64
    assert 0.95 * dot < est_j.flops < 1.05 * dot
    assert 0.95 * dot < est_h.flops < 1.05 * dot
    assert abs(est_j.flops - est_h.flops) < 0.05 * dot


def test_narrow_chain_pricing_scales_with_blocks():
    m = CostModel()
    aval = jax.ShapeDtypeStruct((1024,), jnp.float32)
    chain = lambda x: (x * 2 + 1) * (x - 3)
    one = m.price_jaxpr(jax.make_jaxpr(chain)(aval), nblocks=1)
    four = m.price_jaxpr(jax.make_jaxpr(chain)(aval), nblocks=4)
    assert four.flops == 4 * one.flops
    assert four.hbm_bytes == 4 * one.hbm_bytes
    assert four.dispatches == 4 * one.dispatches
    # 4 arithmetic eqns (mul, add, sub, mul) on 1024 elems
    assert one.flops == 4 * 1024


def test_move_ops_price_bytes_not_flops():
    """Dtype-rot regression: a bf16 add lowers as convert→add→convert; the
    converts move bytes but must not bill flops (they used to)."""
    x = jax.ShapeDtypeStruct((32, 32), jnp.bfloat16)
    r = hlo_cost.analyze(_hlo(lambda v: v + v, x))
    assert r["flops_per_device"] == 32 * 32
    assert r["hbm_bytes_per_device"] >= 2 * 32 * 32 * 2  # in+out at 2B/elem


def test_fp8_and_subbyte_dtypes_price():
    assert hlo_cost.shape_bytes("f8e4m3[64]") == 64
    assert hlo_cost.shape_bytes("f8e5m2fnuz[64]") == 64
    assert hlo_cost.shape_bytes("u2[8]") == 8  # ceiling at byte granularity


def test_predict_seconds_monotone_in_work():
    m = CostModel(DeviceParams())
    small = CostEstimate(flops=1e6, hbm_bytes=1e5, dispatches=1)
    big = CostEstimate(flops=1e9, hbm_bytes=1e8, dispatches=1)
    assert m.predict_s(big) > m.predict_s(small) > 0


def test_fit_rescales_toward_observed():
    m = CostModel()
    est = CostEstimate(flops=1e9)
    before = m.predict_s(est)
    m.fit([(before, 2 * before), (before, 2 * before), (before, 2 * before)])
    assert abs(m.predict_s(est) - 2 * before) / (2 * before) < 1e-6


def test_wide_stage_collective_priced():
    """An 8-way psum prices wire bytes through the parser — the collective
    half of stage pricing (DESIGN.md §13)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.compat import shard_map

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs >1 device")
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    g = shard_map(lambda x: jax.lax.psum(x * 2.0, "data"),
                  mesh=mesh, in_specs=P("data"), out_specs=P())
    txt = jax.jit(g).lower(jnp.ones((n, 16), jnp.float32)).compile().as_text()
    r = hlo_cost.analyze(txt)
    assert r["comm_bytes_total_per_device"] > 0
    assert r["wire_bytes_per_device"] > 0
