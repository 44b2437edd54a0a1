"""The roofline instrument itself: trip-count-aware HLO walking."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import (analyze_hlo_text, parse_collectives,
                                       parse_computations, comp_multipliers)


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_trip_count_multiplies_flops():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=50)
        return out

    comp = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    cost = analyze_hlo_text(comp.as_text())
    expected = 50 * 2 * 64 * 64 * 64
    assert cost.flops == pytest.approx(expected, rel=0.01)


def test_nested_scans_multiply():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None

        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    comp = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    cost = analyze_hlo_text(comp.as_text())
    assert cost.flops == pytest.approx(15 * 2 * 32 ** 3, rel=0.01)


def test_unrolled_dot_flops_exact():
    def f(a, b):
        return a @ b

    comp = _compile(f, jax.ShapeDtypeStruct((128, 256), jnp.float32),
                    jax.ShapeDtypeStruct((256, 64), jnp.float32))
    cost = analyze_hlo_text(comp.as_text())
    assert cost.flops == pytest.approx(2 * 128 * 256 * 64, rel=0.01)
    assert cost.dot_count == 1


def test_peak_estimate_sees_loop_carry():
    def f(x):
        def body(c, _):
            return jnp.tanh(c), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MB carry
    comp = _compile(f, big)
    cost = analyze_hlo_text(comp.as_text(), argument_bytes=4 * 1024 * 1024)
    assert cost.peak_bytes_est >= 8 * 1024 * 1024  # args + carried tuple


def test_collective_parsing_formats():
    hlo = """
ENTRY %main (p: f32[16,64]) -> f32[16,64] {
  %p = f32[16,64]{1,0} parameter(0)
  %ar = f32[16,64]{1,0} all-reduce(%p), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[256,64]{1,0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %cp = f32[16,64]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
    colls = parse_collectives(hlo)
    assert colls["all-reduce"].count == 1
    ar_bytes = 16 * 64 * 4
    assert colls["all-reduce"].wire_bytes == pytest.approx(
        2 * ar_bytes * 15 / 16)
    ag_bytes = 256 * 64 * 2
    assert colls["all-gather"].wire_bytes == pytest.approx(
        ag_bytes * 3 / 4)
    assert colls["collective-permute"].wire_bytes == pytest.approx(
        16 * 64 * 4)


def test_multiplier_map():
    def f(x):
        def body(c, _):
            return c * 2.0, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    comp = _compile(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    comps = parse_computations(comp.as_text())
    mult = comp_multipliers(comps)
    assert any(abs(m - 7.0) < 0.5 for m in mult.values()), mult


def test_collective_parsing_tpu_tiled_layouts():
    """A TPU-compiled module prints tiled layouts, whose parentheses sit
    inside tuple types: the parser reads the op kind past them."""
    hlo = """
ENTRY %main (p: bf16[4,256]) -> (pred[4,256], bf16[64,256]) {
  %p = bf16[4,256]{1,0:T(4,128)(2,1)} parameter(0)
  %ag = bf16[16,256]{1,0:T(8,128)(2,1)S(1)} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = (bf16[4,256]{1,0:T(4,128)(2,1)}, bf16[4,256]{1,0:T(4,128)(2,1)}) all-to-all(%p, %p), replica_groups={{0,1}}
  %cmp = pred[4,256]{1,0:T(4,128)(4,1)S(1)} compare(%p, %p), direction=EQ
  ROOT %t = (pred[4,256]{1,0:T(4,128)(4,1)S(1)}, bf16[16,256]{1,0:T(8,128)(2,1)S(1)}) tuple(%cmp, %ag)
}
"""
    kinds = [op.kind for op in parse_computations(hlo)["__entry__"]]
    assert kinds == ["parameter", "all-gather", "all-to-all", "compare",
                     "tuple"]
    colls = parse_collectives(hlo)
    assert colls["all-gather"].count == 1
    assert colls["all-to-all"].count == 1
