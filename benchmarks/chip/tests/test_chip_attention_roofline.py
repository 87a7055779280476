"""The reader of ``decode_attention_roofline.serve`` on hand-made
reductions of a trace: the bytes it counts at the cell's sizes, checked by
hand, and where it finds nothing to read."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import harness, trace

ROOT = Path(__file__).resolve().parents[3]
NAME = "decode_attention_roofline.serve"
CELL = harness.load_cell("zamba2-serve-offline")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CALLS = 2 * (CELL.traffic["new_tokens"] - 1)  # two traced jobs
# A decode step at the cell's sizes: 32 rows, each reading at a cache of
# 1025..1279 positions (mean 1152) the K and V of 4 invocations, 32 heads
# of 224 in bf16: 32 * 1152 * 4 * 2 * 32 * 224 * 2 bytes.
STEP_BYTES = 4_227_858_432


def _reading(op_s, model=None, calls=CALLS, decode_calls=CALLS):
    red = trace.Reduced(window_s=10.0, busy_s={0: 9.0},
                        module_s={"jit__lambda": 8.0},
                        module_calls={"jit__lambda": calls}, op_s=op_s,
                        collective_s={}, collective_exposed_s={}, gaps=[])
    return harness.Reading(red, {"decode_calls": decode_calls},
                           model or CELL.model, CELL.traffic, PEAK, 1)


def test_counts_the_kv_of_every_invocation_at_the_cell_size():
    assert CELL.traffic["batch"] == 32 and CELL.traffic["prompt_len"] == 1024
    assert STEP_BYTES == 32 * 1152 * 4 * 2 * 32 * 224 * 2
    # every kernel call of the module counts: one an invocation
    per_call_s = 4e-3
    ops = {f"jit__lambda/decode_attention_stacked.{i}":
           per_call_s * CALLS / 4 for i in (8, 9, 10, 11)}
    ops["jit__lambda/fusion.1"] = 5.0  # not the kernel
    ops["jit_prefill/decode_attention_stacked.1"] = 5.0  # another module
    got = harness.read_metric(NAME, _reading(ops))
    assert got == pytest.approx(100 * STEP_BYTES / 819e9 / per_call_s)


def test_reads_nothing_without_the_kernel_or_the_count():
    ops = {"jit__lambda/decode_attention_stacked.3": 1.0}
    assert harness.read_metric(NAME, _reading(
        {"jit__lambda/fusion.1": 1.0})) is None
    dense = json.loads(
        (ROOT / "benchmarks/chip/configs/yi-9b-12l.json").read_text())
    assert harness.read_metric(NAME, _reading(ops, dense["model"])) is None
    assert harness.read_metric(NAME, _reading(
        ops, decode_calls=CALLS + 1)) is None
    assert harness.read_metric(NAME, harness.Reading(
        None, {}, CELL.model, CELL.traffic, PEAK, 1)) is None
