"""The benchmark's own arithmetic: parameters, operations and bytes from
shapes (``flops.py``), and the peaks table (``peaks.json``)."""

import ast
import json
from pathlib import Path

import pytest

from benchmarks.chip import flops, harness

CHIP = Path(__file__).resolve().parents[1]


def _config(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_published_parameter_counts():
    yi12 = _config("yi-9b-12l")["model"]
    yi48 = dict(yi12, n_layers=48)
    assert flops.param_count(yi48) == 8_829_407_232  # Yi-9B: 8.83 B
    assert round(flops.param_count(yi12) / 1e9, 2) == 2.60
    assert flops.layer_params(yi12) == 173_023_232
    mamba = _config("mamba2-130m")["model"]
    assert flops.param_count(mamba) == 128_946_624  # ~129 M


DENSE = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
         "n_kv_heads": 1, "d_head": 4, "d_ff": 16, "vocab": 30}
SSM = {"family": "ssm", "n_layers": 1, "d_model": 4, "ssm_state": 2,
       "ssm_head_dim": 2, "ssm_expand": 2, "ssm_chunk": 4, "ssm_conv": 2,
       "vocab": 10, "tie_embeddings": True}


def test_dense_small_shape_by_hand():
    # layer: q 64 + k,v 2*32 + o 64 = 192; SwiGLU 3*8*16 = 384; norms 16.
    assert flops.layer_params(DENSE) == 592
    # 2 layers, final norm 8, embedding and head 32 (padded) x 8 each.
    assert flops.param_count(DENSE) == 1704
    # per token at seq 7: 2 * (2 * 576 + 256) matmul + attention
    # 2 layers * 4 * 8 * mean keys 4 = 256.
    assert flops.forward_flops_per_token(DENSE, 7) == 3072
    assert flops.train_flops_per_token(DENSE, 7) == 3 * 3072
    # decode, batch 3, 5 keys: per row 2816 + 2 * 4 * 8 * 5.
    assert flops.decode_flops(DENSE, 3, 5) == 3 * 3136
    # bytes: weights (1184 + 8 + 256) * 2, embedding rows 3 * 8 * 2,
    # KV 3 rows * 5 positions * (K, V) * 2 layers * 4 wide * 2 bytes.
    assert flops.decode_bytes(DENSE, 3, 5) == 2896 + 48 + 480
    # prefill, 2 prompts of 3: per token 2304 + attention 2 * 4 * 8 * 2,
    # and the head once per prompt, 2 * 256.
    assert flops.prefill_flops(DENSE, 2, 3) == 2 * (3 * (2304 + 128) + 512)
    # a job: prefill, then decode steps over 4 and 5 keys.
    assert flops.serve_job_flops(DENSE, 2, 3, 3) == (
        flops.prefill_flops(DENSE, 2, 3) + flops.decode_flops(DENSE, 2, 4)
        + flops.decode_flops(DENSE, 2, 5))
    assert flops.serve_job_decode_bytes(DENSE, 2, 3, 3) == (
        flops.decode_bytes(DENSE, 2, 4) + flops.decode_bytes(DENSE, 2, 5)) / 2


def test_ssm_small_shape_by_hand():
    # in_proj 4 * 24, conv 2 * 12, A/D/dt 12, gated norm 8, out 32, norm 4.
    assert flops.layer_params(SSM) == 176
    assert flops.param_count(SSM) == 176 + 4 + 64  # tied embedding
    # matmuls 2 * (128 + 64); SSD: scores 2*4*2 + 4 heads * (16 + 8 + 8);
    # convolution 2 * 2 * 12.
    assert flops.forward_flops_per_token(SSM, 8) == 384 + 16 + 128 + 48
    assert flops.train_flops_per_token(SSM, 8) == 3 * 576


def test_counts_come_from_no_program_module():
    # flops.py and every family file (its counts, weights and reference)
    files = [CHIP / "flops.py", *sorted((CHIP / "reference").glob("*.py"))]
    assert len(files) >= 3
    for path in files:
        tree = ast.parse(path.read_text())
        imported = {alias.name for node in ast.walk(tree)
                    if isinstance(node, ast.Import) for alias in node.names}
        imported |= {node.module for node in ast.walk(tree)
                     if isinstance(node, ast.ImportFrom) and node.module}
        assert not any(name.startswith("repro") for name in imported), \
            (path.name, imported)


def test_peaks_hold_the_v5e_with_a_source():
    table = json.loads((CHIP / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(harness.NoChip, match="no peaks"):
        harness.load_peaks(kind)
