"""HLO analyzer tests: parser, roofline terms, collective accounting,
critical path, and while-loop LCD — on real compiled modules (8 host-device
SPMD in a subprocess-safe way: these tests run under the default 1-device
runtime and use handwritten HLO text plus small jit'd modules)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo import (
    TPU_V5E, hlo_critical_path, hlo_loop_carried, parse_hlo,
    roofline_report,
)
from repro.core.hlo.costs import HLOCostModel
from repro.core.hlo.roofline import collective_stats

SIMPLE_HLO = """
HloModule test_module, num_partitions=4

%add_red (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %y = f32[8,128]{1,0} multiply(%x, %x)
  %z = f32[8,128]{1,0} all-reduce(%y), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add_red
  ROOT %t = (s32[], f32[8,128]) tuple(%i2, %z)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (arg: f32[8,128], w: f32[128,256]) -> f32[8,256] {
  %arg = f32[8,128]{1,0} parameter(0)
  %w = f32[128,256]{1,0} parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,128]) tuple(%zero, %arg)
  %loop = (s32[], f32[8,128]) while(%init), condition=%cond, body=%body
  %out = f32[8,128]{1,0} get-tuple-element(%loop), index=1
  ROOT %dot = f32[8,256]{1,0} dot(%out, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_parse_structure():
    mod = parse_hlo(SIMPLE_HLO)
    assert mod.num_partitions == 4
    assert mod.entry_name == "main"
    assert len(mod.computations) == 4
    dot = mod.entry.op_by_name("dot")
    assert dot.opcode == "dot" and dot.is_root
    assert dot.shapes[0].dims == (8, 256)
    assert dot.shapes[0].bytes == 8 * 256 * 4


def test_dot_flops():
    mod = parse_hlo(SIMPLE_HLO)
    cm = HLOCostModel(mod, TPU_V5E)
    dot = mod.entry.op_by_name("dot")
    assert cm.op_flops(dot, mod.entry) == 2 * 8 * 256 * 128


def test_while_trip_count_from_compare():
    mod = parse_hlo(SIMPLE_HLO)
    cm = HLOCostModel(mod, TPU_V5E)
    loop = mod.entry.op_by_name("loop")
    assert cm.while_trip_count(loop) == 10


def test_collectives_scaled_by_trip_count():
    mod = parse_hlo(SIMPLE_HLO)
    cm = HLOCostModel(mod, TPU_V5E)
    stats = collective_stats(mod, TPU_V5E, exec_counts=cm.execution_counts())
    assert stats.counts["all-reduce"] == 10
    assert stats.total_bytes == pytest.approx(10 * 8 * 128 * 4)


def test_lcd_finds_loop_carried_chain():
    res = hlo_loop_carried(SIMPLE_HLO)
    assert res.chains
    longest = res.longest
    assert longest.trip_count == 10
    # The f32 state (index 1) chain should dominate the counter chain.
    assert longest.tuple_index == 1
    assert any("all-reduce" in op or op == "z" for op in longest.ops)


def test_critical_path_spans_loop_and_dot():
    cp = hlo_critical_path(SIMPLE_HLO)
    opcodes = [n.opcode for n in cp.path]
    assert "while" in opcodes and "dot" in opcodes
    assert cp.seconds > 0


def test_roofline_report_from_text():
    rep = roofline_report(SIMPLE_HLO, name="unit",
                          model_flops=2 * 8 * 256 * 128 * 4)
    assert rep.num_partitions == 4
    assert set(rep.terms) == {"MXU", "HBM", "ICI"}
    assert rep.collective.total_bytes > 0
    assert rep.dominant in ("MXU", "HBM", "ICI")
    assert "bound" in rep.render() or rep.render()


def test_roofline_on_compiled_module():
    """End-to-end on a real compiled artifact (1 device)."""
    from repro.core.hlo import roofline_from_compiled

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=6)
        return y.sum()

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    rep = roofline_from_compiled(compiled, name="t",
                                 model_flops=2 * 64 * 64 * 64 * 6)
    # Trip-aware correction must recover the 6x of the scan.
    assert rep.useful_ratio is not None
    assert 0.3 < rep.useful_ratio < 1.5
    lcd = hlo_loop_carried(compiled)
    assert lcd.chains and lcd.longest.trip_count == 6


def test_known_trip_count_backend_config():
    hlo = SIMPLE_HLO.replace(
        "while(%init), condition=%cond, body=%body",
        'while(%init), condition=%cond, body=%body, '
        'backend_config={"known_trip_count":{"n":"7"}}')
    mod = parse_hlo(hlo)
    cm = HLOCostModel(mod, TPU_V5E)
    loop = mod.entry.op_by_name("loop")
    assert cm.while_trip_count(loop) == 7


def test_tuple_type_with_index_comments():
    """HLO inserts /*index=N*/ comments in wide tuple types."""
    line = ("  %w = (s32[], f32[4,4]{1,0}, /*index=2*/f32[8]) "
            "while(%t), condition=%c, body=%b")
    mod = parse_hlo("ENTRY %e (p: s32[]) -> s32[] {\n" + line + "\n}")
    op = mod.entry.op_by_name("w")
    assert op is not None and op.opcode == "while"
    assert len(op.shapes) == 3


def _conv_macs_brute(n, c, h, o, k, stride, pad, lhs_dil, rhs_dil):
    """Multiply-adds of a 1-D NCH convolution, tap by tap."""
    out = ((h - 1) * lhs_dil + 1 + pad[0] + pad[1]
           - ((k - 1) * rhs_dil + 1)) // stride + 1
    taps = sum(1 for oo in range(out) for kk in range(k)
               if 0 <= oo * stride + kk * rhs_dil - pad[0] <= (h - 1) * lhs_dil
               and (oo * stride + kk * rhs_dil - pad[0]) % lhs_dil == 0)
    return n * o * c * taps


@pytest.mark.parametrize("h,k,stride,pad,lhs_dil,rhs_dil", [
    (13, 4, 1, (0, 0), 1, 1),
    (13, 4, 2, (1, 2), 1, 1),
    (13, 4, 1, (3, 3), 2, 1),
    (13, 4, 3, (2, 0), 1, 2),
    (13, 4, 2, (3, 3), 2, 1),
    (13, 5, 3, (4, 1), 2, 2),
    # A batch dim written as a spatial one, as the TPU compiler writes dots:
    # a window of 32 over one real element padded by 31 on each side.
    (1, 32, 1, (31, 31), 1, 1),
])
def test_convolution_flops_count_real_taps(h, k, stride, pad, lhs_dil,
                                           rhs_dil):
    x = jax.ShapeDtypeStruct((2, 8, h), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 8, k), jnp.float32)

    def conv(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (stride,), [pad], lhs_dilation=(lhs_dil,),
            rhs_dilation=(rhs_dil,))

    module = parse_hlo(jax.jit(conv).lower(x, w).compile().as_text())
    flops = HLOCostModel(module, TPU_V5E).module_flops()
    assert flops == 2 * _conv_macs_brute(2, 8, h, 5, k, stride, pad,
                                         lhs_dil, rhs_dil)
