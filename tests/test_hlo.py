"""Loop-aware HLO analyzer: trip counts, dot flops, collective parsing."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_analysis import HloCostModel, _shape_bytes, parse_hlo


def test_shape_bytes():
    assert _shape_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
    assert _shape_bytes("(f32[8], s32[2,2])") == 32 + 16
    assert _shape_bytes("pred[]") == 1


def test_scan_trip_count_multiplies_flops():
    def scanned(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    comp = jax.jit(scanned).lower(x, ws).compile()
    t = HloCostModel(comp.as_text()).analyze()
    expect = 8 * 2 * 128 * 256 * 256
    assert 0.95 < t.flops / expect < 1.15  # dots dominate; tanh adds a little


def test_nested_scan_trip_counts():
    def inner(c, w):
        return jnp.tanh(c @ w), None

    def outer(x, ws):
        def body(c, _):
            c2, _ = jax.lax.scan(inner, c, ws)
            return c2, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    comp = jax.jit(outer).lower(x, ws).compile()
    t = HloCostModel(comp.as_text()).analyze()
    expect = 3 * 4 * 2 * 64 * 64 * 64
    assert 0.9 < t.flops / expect < 1.3


def test_stock_cost_analysis_undercounts_loops():
    """The reason this module exists."""
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((16, 128, 128), jnp.float32)
    comp = jax.jit(scanned).lower(x, ws).compile()
    stock = comp.cost_analysis()["flops"]
    ours = HloCostModel(comp.as_text()).analyze().flops
    assert ours > 10 * stock  # 16 iterations vs 1


SHARDED_SNIPPET = """\
HloModule test

%body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,64] get-tuple-element(%p), index=1
  %ar = f32[64,64] all-reduce(%x), channel_id=1, replica_groups=[4,64]<=[256], to_apply=%add
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,64]) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[64,64])) -> pred[] {
  %p = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64] parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[64,64]) tuple(%zero, %a)
  %w = (s32[], f32[64,64]) while(%t0), condition=%cond, body=%body
  %ag = f32[128,64] all-gather(%a), channel_id=2, replica_groups=[8,32]<=[256], dimensions={0}
  ROOT %out = f32[64,64] get-tuple-element(%w), index=1
}
"""


def test_collectives_inside_while_counted_with_trips():
    m = HloCostModel(SHARDED_SNIPPET)
    t = m.analyze()
    ar_bytes = 64 * 64 * 4 * 12        # 12 loop iterations
    ag_bytes = 128 * 64 * 4
    assert t.collective_bytes["all-reduce"] == ar_bytes
    assert t.collective_bytes["all-gather"] == ag_bytes
    assert t.collective_counts["all-reduce"] == 12
    assert t.collective_by_group[("all-reduce", 64)] == ar_bytes
    assert t.collective_by_group[("all-gather", 32)] == ag_bytes


def test_trip_count_from_condition():
    m = HloCostModel(SHARDED_SNIPPET)
    assert m.trip_count("cond") == 12


def test_parse_handles_tuple_params():
    comps = parse_hlo(SHARDED_SNIPPET)
    assert set(comps) >= {"body", "cond", "main"}
    assert any(i.opcode == "while" for i in comps["main"].instructions)


# the TPU's text: tiled layouts after every shape, asynchronous pairs, and a
# reduce-scatter written as an all-reduce fused with each device's slice
TPU_SNIPPET = """\
HloModule tpu

%add (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %s = bf16[] add(%x, %y)
}

%all-reduce-scatter.1 (input: bf16[8,128]) -> bf16[4,128] {
  %input = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %ar = bf16[8,128]{1,0:T(8,128)(2,1)} all-reduce(%input), channel_id=3, replica_groups={{0,1},{2,3}}, to_apply=%add
  %i = u32[]{:T(128)} partition-id()
  %z = u32[] constant(0)
  ROOT %ds = bf16[4,128]{1,0:T(8,128)(2,1)S(1)} dynamic-slice(%ar, %i, %z), dynamic_slice_sizes={4,128}
}

ENTRY %main (a: bf16[8,128]) -> bf16[4,128] {
  %a = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %ag = bf16[16,128]{1,0:T(8,128)(2,1)S(1)} all-gather(%a), channel_id=1, replica_groups=[2,2]<=[4], dimensions={0}
  %cps = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8,128]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%a), channel_id=2, source_target_pairs={{0,1},{1,0}}
  %cpd = bf16[8,128]{1,0:T(8,128)(2,1)} collective-permute-done(%cps)
  ROOT %rs = bf16[4,128]{1,0:T(8,128)(2,1)} fusion(%cpd), kind=kCustom, calls=%all-reduce-scatter.1
}
"""


def test_tpu_layouts_async_pairs_and_fused_reduce_scatter():
    t = HloCostModel(TPU_SNIPPET).analyze()
    assert dict(t.collective_counts) == {"all-gather": 1, "collective-permute": 1,
                                        "reduce-scatter": 1}
    assert t.collective_bytes["all-gather"] == 16 * 128 * 2
    assert t.collective_bytes["collective-permute"] == 8 * 128 * 2   # output once
    assert t.collective_bytes["reduce-scatter"] == 4 * 128 * 2       # the slice kept
    assert t.collective_by_group[("all-gather", 2)] == 16 * 128 * 2
    assert t.collective_by_group[("reduce-scatter", 2)] == 4 * 128 * 2
