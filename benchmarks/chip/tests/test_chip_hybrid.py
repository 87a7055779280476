"""The hybrid family's equations on the CPU, three ways.

- The program (``ServeEngine``: prefill, then decode through its caches)
  against the family's float32 reference (``reference/hybrid.py``), at
  the family's CPU sizes: both shared blocks run twice, two invocations
  back to back, two groups of B and C.
- The reference against ``transformers``' ``Zamba2ForCausalLM`` with the
  same weights loaded into it, so that a misread equation (the block's
  order, which adapter an invocation takes, the (d_head / 2) scale, the
  grouped norm) shows.
- The parameter count against ``Zamba2ForCausalLM`` built on the meta
  device (nothing allocated) at the published configuration, whole and cut
  to the cell's 24 layers.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops, traffic, weights
from benchmarks.chip.reference import hybrid

ROOT = Path(__file__).resolve().parents[3]
CONFIG = json.loads(
    (ROOT / "benchmarks/chip/configs/zamba2-7b-24l.json").read_text())
MODEL = CONFIG["model"]
SEEDS = [5, 2**33 + 7]


def tiny(**changes):
    return dict(MODEL, **hybrid.TINY, **changes)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_serves_what_the_reference_computes(seed):
    """Every served token's logits (prefill's, then each decode step's)
    agree with the reference's causal pass over the prompt and the tokens
    before it.  Both compute in float32; they differ in order of summation
    only (the chunked SSD scan against the step-by-step recurrence, online
    softmax against the whole one), ~1e-6 of logits that spread by ~1.2."""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.serving import ServeEngine

    m = tiny(dtype="float32")
    root = weights.root_key(seed)
    params = weights.params_fn(m, jnp.float32)(root)
    engine = ServeEngine(ModelConfig(**m), params,
                         run=RunConfig(**CONFIG["run"]), batch_size=2,
                         max_len=28)
    prompts = traffic.job_prompts({"batch": 2, "prompt_len": 16},
                                  m["vocab"], seed, 0)
    results = engine.generate(prompts, max_new_tokens=12, return_logits=True)
    seqs = np.asarray([p + r.tokens[:-1] for p, r in zip(prompts, results)],
                      np.int32)
    ref = np.asarray(hybrid.logits(m, root, seqs, 15, jnp.float32)["f32"])
    got = np.stack([r.logits for r in results])
    v = m["vocab"]
    assert ref[..., :v].std() > 0.5
    np.testing.assert_allclose(got[..., :v], ref[..., :v], rtol=0, atol=1e-4)


def _hf_config(m, **extra):
    from transformers import Zamba2Config

    pattern = m["hybrid_pattern"][:m["n_layers"]]
    return Zamba2Config(
        vocab_size=m["vocab"], hidden_size=m["d_model"],
        num_hidden_layers=m["n_layers"],
        layers_block_type=["hybrid" if c == "H" else "mamba"
                           for c in pattern],
        mamba_d_state=m["ssm_state"], mamba_d_conv=m["ssm_conv"],
        mamba_expand=m["ssm_expand"], mamba_ngroups=m["ssm_groups"],
        n_mamba_heads=m["ssm_expand"] * m["d_model"] // m["ssm_head_dim"],
        use_conv_bias=m["ssm_conv_bias"], chunk_size=m["ssm_chunk"],
        intermediate_size=m["d_ff"], hidden_act="gelu",
        num_attention_heads=m["n_heads"], num_key_value_heads=m["n_kv_heads"],
        num_mem_blocks=m["hybrid_blocks"], adapter_rank=m["adapter_rank"],
        use_shared_mlp_adapter=True, use_shared_attention_adapter=False,
        use_mem_rope=True, rope_theta=m["rope_theta"],
        rms_norm_eps=m["norm_eps"], tie_word_embeddings=True, **extra)


def _load(model, params, m):
    """The seed's weights into ``Zamba2ForCausalLM`` (torch: (out, in))."""
    import torch

    def t(a):
        return torch.from_numpy(np.array(a, np.float32))

    ids = hybrid.hybrid_ids(m)
    sd = {"model.embed_tokens.weight": t(params["embed"][:m["vocab"]]),
          "model.final_layernorm.weight": t(params["final_norm"])}
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        mp = lp["mamba"]
        pre = f"model.layers.{i}."
        if i in ids:
            j = ids.index(i)
            blk = jax.tree.map(lambda a: a[j % m["hybrid_blocks"]],
                               params["shared"])
            own = jax.tree.map(lambda a: a[j], params["invocations"])
            st = pre + "shared_transformer."
            sd[pre + "linear.weight"] = t(own["linear"].T)
            for name in ("q", "k", "v", "o"):
                sd[f"{st}self_attn.{name}_proj.weight"] = t(
                    blk["attn"][f"w{name}"].T)
            ff = st + "feed_forward."
            sd[ff + "gate_up_proj.weight"] = t(blk["mlp"]["wi"].T)
            sd[ff + "down_proj.weight"] = t(blk["mlp"]["wo"].T)
            for jj in range(j % m["hybrid_blocks"], len(ids),
                            m["hybrid_blocks"]):  # the block's adapters
                adapters = params["invocations"]
                sd[f"{ff}gate_up_proj_adapter_list.{jj}.0.weight"] = t(
                    adapters["adapter_down"][jj].T)
                sd[f"{ff}gate_up_proj_adapter_list.{jj}.1.weight"] = t(
                    adapters["adapter_up"][jj].T)
            sd[st + "input_layernorm.weight"] = t(blk["norm1"])
            sd[st + "pre_ff_layernorm.weight"] = t(blk["norm2"])
            pre += "mamba_decoder."
        mx = pre + "mamba."
        sd[mx + "in_proj.weight"] = t(mp["in_proj"].T)
        sd[mx + "conv1d.weight"] = t(mp["conv_w"].T[:, None, :])
        sd[mx + "conv1d.bias"] = t(mp["conv_b"])
        for name in ("A_log", "D", "dt_bias"):
            sd[mx + name] = t(mp[name])
        sd[mx + "norm.weight"] = t(mp["ssm_norm"])
        sd[mx + "out_proj.weight"] = t(mp["out_proj"].T)
        sd[pre + "input_layernorm.weight"] = t(lp["norm1"])
    missing, unexpected = model.load_state_dict(sd, strict=False)
    # A shared block, its adapters with it, appears under each hybrid layer
    # that runs it; lm_head is the embedding.
    assert not unexpected, unexpected
    assert set(missing) <= {"lm_head.weight"}, missing


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_transformers(seed, monkeypatch):
    """``Zamba2ForCausalLM`` (float32, its plain-PyTorch path) with the
    seed's weights gives the reference's logits at every position.  Its
    ``time_step_min`` floor on dt, which only that path applies, is set
    below any dt drawn (the reference follows the fused kernels, which
    apply none); both compute in float32, so the logits agree to float32
    summation order, ~1e-6 of a spread of ~1.2."""
    monkeypatch.setenv("USE_TF", "0")
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import Zamba2ForCausalLM

    m = tiny()
    root = weights.root_key(seed)
    params = weights.params_fn(m, jnp.float32)(root)
    model = Zamba2ForCausalLM(_hf_config(
        m, time_step_min=1e-9, attn_implementation="eager")).eval()
    _load(model, params, m)
    tokens = np.asarray(traffic.job_prompts(
        {"batch": 2, "prompt_len": 24}, m["vocab"], seed, 0), np.int64)
    with torch.no_grad():
        want = model(torch.from_numpy(tokens), use_cache=False).logits
    got = hybrid.logits(m, root, tokens.astype(np.int32), 0,
                        jnp.float32)["f32"]
    v = m["vocab"]
    np.testing.assert_allclose(np.asarray(got)[..., :v], want.numpy(),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("n_layers,count", [(81, 7356749648),
                                            (24, 2733050240)])
def test_param_count_is_transformers(n_layers, count, monkeypatch):
    """``flops.param_count`` and the program's ``ModelConfig.param_count``
    of the cell's configuration, whole and as cut, count what
    ``Zamba2ForCausalLM`` holds (its tied embedding once)."""
    monkeypatch.setenv("USE_TF", "0")
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import Zamba2ForCausalLM
    from repro.configs.archs import ZAMBA2_7B_PATTERN
    from repro.configs.base import ModelConfig

    m = dict(MODEL, n_layers=n_layers, hybrid_pattern=ZAMBA2_7B_PATTERN)
    assert ZAMBA2_7B_PATTERN == "".join(
        "H" if kind == "hybrid" else "M"
        for kind in CONFIG["layers_block_type"])
    with torch.device("meta"):
        model = Zamba2ForCausalLM(_hf_config(m))
    assert sum(p.numel() for p in model.parameters()) == count
    assert flops.param_count(m) == count
    assert ModelConfig(**m).param_count() == count
