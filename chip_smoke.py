"""Run the model runtime's main path once on one TPU v5e, and check it.

    python chip_smoke.py               # one chip: train, serve, analyze
    python chip_smoke.py --four-chips  # four chips: the sharded train step
                                       # beside the same steps on one chip

Everything runs in this one process, at tinyllama-1.1b's published widths
(d_model 2048, 22 layers, 32/4 heads, d_ff 5632, vocab 32000) with random
weights from ``SEED``:

(a) train  -- ``repro.launch.train.train_loop`` takes a few steps; the step-1
    loss must be finite and within 10% of ln(vocab), every grad norm finite.
(b) serve  -- ``ServeEngine.generate`` answers requests of two prompt
    lengths, interleaved; the logits behind every generated token must agree
    with the causal forward pass (the computation ``prefill`` ends in) over
    the prompt plus the tokens generated before it.
(c) analyze -- ``repro.api.analyze(compiled, arch=...)`` brackets the compiled
    train and decode steps; the bracket is printed beside the measured step
    time of the same compiled program (host clock around
    ``block_until_ready``, compilation excluded).

With ``--four-chips`` only the 22-layer train step runs, on a 2x2
(data, model) mesh with its state placed by ``state_shardings`` (FSDP +
ZeRO), and then unsharded on one of the four chips; the losses and grad
norms must agree.

The script runs on the TPU even where ``JAX_PLATFORMS`` names the CPU.  It
fails, printing no result, when the host has no TPU, when the device
is not one whose peaks the analyzer holds, and when any check fails.  The
last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Analyzer target for each device kind whose peaks the repository holds.
ARCH_BY_DEVICE_KIND = {"TPU v5 lite": "tpu-v5e"}

MODEL = "tinyllama-1.1b"
SEED = 0
# The whole train state (bf16 params, f32 Adam moments) is 10.25 GiB, so the
# step keeps all 22 layers and cuts the batch: 2 x 1024 tokens compiles to
# 14.3 GiB of the chip's 15.75 (memory_analysis of the v5e compile).
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 4
# Serving: four requests of two lengths, interleaved; the engine groups them
# into two waves of one length each.  The timed decode steps run one wave of
# the longer prompts.
PROMPT_LENS = (37, 100, 100, 37)
SERVE_BATCH, NEW_TOKENS = 2, 8
# Decode vs. forward logits: relative RMS error of each logit vector.  Both
# paths keep activations, K and V in bf16 (unit roundoff 2^-9), and their
# rounding differs through 22 layers: about 3% measured at full width on the
# CPU.  A cache slot or position off by one measures about 100%, and fp8-level
# rounding (16x bf16's) would exceed 10%.
LOGIT_RTOL = 0.1
# Sharded vs. unsharded training: the same bf16 math with reductions split
# differently across chips.
LOSS_RTOL, GNORM_RTOL = 1e-3, 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# (a) train
# ---------------------------------------------------------------------------


def train_phase(cfg, mesh_ctx, *, sharded=False):
    """Run train_loop; returns its TrainRun after checking the metrics.
    ``sharded`` adds FSDP + ZeRO to the mesh's tensor-parallel rules."""
    from repro.configs import RunConfig
    from repro.launch.train import train_loop
    run = RunConfig(attention_impl="chunked", attention_chunk=512,
                    remat="full", zero=sharded, fsdp=sharded,
                    warmup_steps=TRAIN_STEPS, total_steps=10 * TRAIN_STEPS)
    out = train_loop(cfg, run, steps=TRAIN_STEPS, global_batch=TRAIN_BATCH,
                     seq_len=TRAIN_SEQ, seed=SEED, mesh_ctx=mesh_ctx,
                     log_every=1)
    first = out.metrics[0]
    ln_v = math.log(cfg.vocab)
    check(math.isfinite(first["loss"]), f"step-1 loss {first['loss']}")
    check(abs(first["loss"] - ln_v) <= 0.1 * ln_v,
          f"step-1 loss {first['loss']:.4f} not within 10% of "
          f"ln({cfg.vocab}) = {ln_v:.4f}")
    for m in out.metrics:
        check(math.isfinite(m["grad_norm"]),
              f"grad_norm {m['grad_norm']} at step {m['step']}")
    say(f"train ok: step-1 loss {first['loss']:.4f} (ln V {ln_v:.4f}), "
        f"grad norms {[round(m['grad_norm'], 4) for m in out.metrics]}")
    return out


# ---------------------------------------------------------------------------
# (b) serve
# ---------------------------------------------------------------------------


def serve_phase(cfg):
    """Generate through ServeEngine and hold the logits behind every token
    to the causal forward pass; then time decode steps of one wave.  Returns
    the compiled decode step and its median time in seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_params
    from repro.models.transformer import forward_hidden, lm_logits
    from repro.serving import ServeEngine

    params = init_params(cfg, jax.random.PRNGKey(SEED))
    engine = ServeEngine(cfg, params, batch_size=SERVE_BATCH)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist()
               for n in PROMPT_LENS]
    t0 = time.perf_counter()
    results = engine.generate(prompts, max_new_tokens=NEW_TOKENS,
                              return_logits=True)
    gen_s = time.perf_counter() - t0
    check([len(r.tokens) for r in results] == [NEW_TOKENS] * len(prompts),
          "every request gets max_new_tokens tokens")

    # Reference: one causal forward over each prompt + all its tokens,
    # right-padded to a common length (causality keeps pads out of reach).
    seqs = [r.prompt + r.tokens for r in results]
    chunk = engine.run.attention_chunk
    width = -(-max(map(len, seqs)) // chunk) * chunk
    ref_in = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ref_in[i, :len(s)] = s

    @jax.jit
    def forward_logits(p, tokens):
        hidden, _ = forward_hidden(p, cfg, engine.run, tokens)
        return lm_logits(p, cfg, hidden)

    ref = np.asarray(forward_logits(params, jnp.asarray(ref_in)), np.float32)
    worst, worst_abs = 0.0, 0.0
    for r in results:
        # Row k chose token k: from prefill (k = 0) or decode step k.
        for k, got in enumerate(r.logits):
            want = ref[r.request_id, len(r.prompt) + k - 1]
            err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            worst = max(worst, err)
            worst_abs = max(worst_abs, float(np.max(np.abs(got - want))))
            check(err <= LOGIT_RTOL,
                  f"request {r.request_id} token {k + 1}: relative logit "
                  f"error {err:.4f} > {LOGIT_RTOL}")
    say(f"serve ok: {len(results)} requests, prompt lengths "
        f"{list(PROMPT_LENS)}, {NEW_TOKENS} tokens each; prefill/decode vs "
        f"forward relative logit error max {worst:.4f} (tolerance "
        f"{LOGIT_RTOL}), max |logit diff| {worst_abs:.4f}")

    # Time one wave's decode steps; every step runs the one compiled program.
    long = max(PROMPT_LENS)
    wave = [p for p in prompts if len(p) == long][:SERVE_BATCH]
    logits, cache = engine.prefill_wave(wave, NEW_TOKENS)
    tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    decode = engine.decode.lower(params, cache, tokens).compile()
    times = []
    for _ in range(NEW_TOKENS):
        t0 = time.perf_counter()
        logits, cache = decode(params, cache, tokens)
        jax.block_until_ready(logits)
        times.append(time.perf_counter() - t0)
        tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    decode_s = statistics.median(times)
    say(f"smoke timing (one run, host clock, not a benchmark): generate "
        f"{gen_s:.2f}s incl. compilation; decode step median "
        f"{decode_s * 1e3:.3f} ms over {len(times)} steps (batch "
        f"{len(wave)}, prompt {long}, cache {long + NEW_TOKENS})")
    return decode, decode_s


# ---------------------------------------------------------------------------
# (c) analyze
# ---------------------------------------------------------------------------


def analyze_phase(name, compiled, measured_s, arch):
    """Bracket one compiled step and print it beside the measured time."""
    from repro.api import analyze
    t0 = time.perf_counter()
    report = analyze(compiled, arch=arch, name=name)
    took = time.perf_counter() - t0
    tp, cp, lcd = report.tp_block, report.cp_block, report.lcd_block
    check(all(math.isfinite(x) for x in (tp, cp, lcd)),
          f"{name}: bracket not finite ({tp}, {lcd}, {cp})")
    check(0.0 < tp <= cp, f"{name}: bracket TP {tp} CP {cp} not ordered")
    where = ("inside" if tp <= measured_s <= cp else
             "below" if measured_s < tp else "above")
    say(f"bracket {name} [{arch}]: TP {tp * 1e3:.3f} ms, LCD "
        f"{lcd * 1e3:.3f} ms, CP {cp * 1e3:.3f} ms "
        f"(bottleneck {report.bottleneck_port}); measured "
        f"{measured_s * 1e3:.3f} ms, {where} the bracket "
        f"(analysis took {took:.1f}s)")
    return report


# ---------------------------------------------------------------------------
# One chip, four chips
# ---------------------------------------------------------------------------


def one_chip(cfg, arch):
    import jax
    from repro.launch.mesh import make_elastic_mesh_context

    out = train_phase(cfg, make_elastic_mesh_context(1))
    train_s = statistics.median([m["step_s"] for m in out.metrics[1:]])
    say(f"smoke timing (one run, host clock, not a benchmark): train step "
        f"median {train_s * 1e3:.3f} ms over {len(out.metrics) - 1} steps "
        f"(batch {TRAIN_BATCH} x seq {TRAIN_SEQ})")
    train_compiled = out.compiled_step
    jax.tree.map(lambda x: x.delete(), out.state)  # free HBM for serving
    del out

    decode_compiled, decode_s = serve_phase(cfg)

    analyze_phase("train_step", train_compiled, train_s, arch)
    analyze_phase("decode_step", decode_compiled, decode_s, arch)


def bytes_in_use(device) -> int:
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory stats")
    return stats["bytes_in_use"]


def four_chips(cfg):
    import jax
    import numpy as np
    from repro.launch.mesh import make_elastic_mesh_context

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
          f"JAX finds {len(jax.devices())}")
    sharded_ctx = make_elastic_mesh_context(4, model_parallel=2)
    say(f"sharded: mesh {dict(sharded_ctx.mesh.shape)}, FSDP + ZeRO")
    sharded = train_phase(cfg, sharded_ctx, sharded=True)
    leaves = jax.tree.leaves(sharded.state)
    state_bytes = sum(x.nbytes for x in leaves)
    for d in jax.devices():
        held = sum(s.data.nbytes for x in leaves for s in x.addressable_shards
                   if s.device == d)
        say(f"device {d.id}: bytes_in_use {bytes_in_use(d) / 2**30:.3f} GiB,"
            f" train state shards {held / 2**30:.3f} GiB of "
            f"{state_bytes / 2**30:.3f}")
        check(held < 0.5 * state_bytes,
              f"device {d.id} holds {held} of {state_bytes} state bytes")
    jax.tree.map(lambda x: x.delete(), sharded.state)

    say("unsharded: the same steps on device 0")
    single = train_phase(cfg, make_elastic_mesh_context(1))
    for a, b in zip(sharded.metrics, single.metrics):
        check(np.isclose(a["loss"], b["loss"], rtol=LOSS_RTOL, atol=0),
              f"step {a['step']}: loss sharded {a['loss']} vs one chip "
              f"{b['loss']} (rtol {LOSS_RTOL})")
        check(np.isclose(a["grad_norm"], b["grad_norm"], rtol=GNORM_RTOL,
                         atol=0),
              f"step {a['step']}: grad norm sharded {a['grad_norm']} vs one "
              f"chip {b['grad_norm']} (rtol {GNORM_RTOL})")
    say("four chips ok: losses " + ", ".join(
        f"{a['loss']:.5f}/{b['loss']:.5f}"
        for a, b in zip(sharded.metrics, single.metrics))
        + " (sharded/one chip)")
    for name, out in (("sharded", sharded), ("one chip", single)):
        step_s = statistics.median([m["step_s"] for m in out.metrics[1:]])
        say(f"smoke timing (one run, host clock, not a benchmark): {name} "
            f"train step median {step_s * 1e3:.3f} ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on a 2x2 mesh "
                         "and its one-chip comparison")
    args = ap.parse_args()

    import jax
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id

    # Look for TPU chips on the PCI bus before any backend starts, so that a
    # host without one never starts the TPU runtime.  Where there is one, the
    # smoke runs on it whatever JAX_PLATFORMS says (test environments set it
    # to "cpu"), and never falls back to the CPU.
    chips, _ = num_available_tpu_chips_and_device_id()
    if chips == 0:
        print("chip_smoke: no TPU chip on this host", file=sys.stderr)
        return 2
    jax.config.update("jax_platforms", "tpu")
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {device.platform!r})",
              file=sys.stderr)
        return 2
    arch = ARCH_BY_DEVICE_KIND.get(device.device_kind)
    if arch is None:
        print(f"chip_smoke: no analyzer peaks for device kind "
              f"{device.device_kind!r}; known: {sorted(ARCH_BY_DEVICE_KIND)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    say(f"device: {device.platform} {device.device_kind} x "
        f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
    cfg = get_config(MODEL)
    say(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; no width or depth cut")
    say(f"reduced: train batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, "
        f"{TRAIN_STEPS} steps (TinyLlama pretrained at seq 2048, ~2M tokens "
        f"per step)")
    if not args.four_chips:
        say(f"reduced: serve {len(PROMPT_LENS)} requests, prompts "
            f"{list(PROMPT_LENS)}, {NEW_TOKENS} new tokens, batch "
            f"{SERVE_BATCH}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(cfg)
        else:
            one_chip(cfg, arch)
    except SmokeFailure as exc:
        print(f"chip_smoke: check failed: {exc}", file=sys.stderr)
        return 1
    say(f"smoke passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
