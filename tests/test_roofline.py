"""Roofline HLO analyzer: trip counts, collective traffic, flops."""
import jax
import jax.numpy as jnp

from repro.core.roofline import collective_bytes, hlo_stats


def test_scan_trip_count_flops():
    """jit(scan of 10 matmuls) must report 10x one matmul's flops —
    the exact case where XLA's cost_analysis reports 1x."""
    def one(x, w):
        return x @ w

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w1 = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w10 = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    f1 = hlo_stats(jax.jit(one).lower(x, w1).compile().as_text())["flops"]
    f10 = hlo_stats(jax.jit(scanned).lower(x, w10).compile().as_text()
                    )["flops"]
    expected = 2 * 128 ** 3
    assert abs(f1 - expected) / expected < 0.05
    assert abs(f10 - 10 * expected) / (10 * expected) < 0.05


def test_collective_ring_traffic_parsing():
    hlo = """
HloModule test

ENTRY %main (a: f32[1024,256]) -> f32[1024,256] {
  %a = f32[1024,256] parameter(0)
  %ar = f32[1024,256] all-reduce(%a), replica_groups=[4,8]<=[32]T(0), to_apply=%sum
  ROOT %ag = f32[1024,256] all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
    out = collective_bytes(hlo)
    r = 1024 * 256 * 4
    assert abs(out["all-reduce"] - 2 * r * 7 / 8) < 1
    assert abs(out["all-gather"] - r * 3 / 4) < 1
    assert out["count"] == 2


def test_async_pairs_counted_once():
    hlo = """
HloModule t

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64] parameter(0)
  %s = f32[64] all-gather-start(%a), replica_groups={{0,1}}, dimensions={0}
  ROOT %d = f32[64] all-gather-done(%s)
}
"""
    out = collective_bytes(hlo)
    assert out["count"] == 1


def test_while_body_collectives_multiplied():
    """Collectives inside a lax.scan body scale with trip count."""
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P

    def f(x, ws):
        def body(c, w):
            y = c @ w
            return jax.lax.with_sharding_constraint(y, P()), None
        out, _ = jax.lax.scan(body, x, ws)
        return out.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    with jax.set_mesh(mesh):
        txt = jax.jit(f).lower(x, ws).compile().as_text()
    st = hlo_stats(txt)
    expected = 7 * 2 * 64 ** 3
    assert abs(st["flops"] - expected) / expected < 0.05


def test_dtype_sizes():
    hlo = """
HloModule t

ENTRY %main (a: bf16[100]) -> bf16[100] {
  %a = bf16[100] parameter(0)
  ROOT %ar = bf16[100] all-reduce(%a), replica_groups={{0,1}}, to_apply=%s
}
"""
    out = collective_bytes(hlo)
    assert abs(out["all-reduce"] - 2 * 200 * 0.5) < 1   # 2·R·(N−1)/N, N=2
