"""The program's outputs for the benchmark's configurations, at the CPU sizes
of their family files, pinned bit for bit.

The hybrid family shares code with both cells: the attention block and the
stacked decode read with ``yi9b-serve-offline``, the Mamba-2 block and its
chunked SSD scan with ``mamba2-train-2k``.  The values below were recorded
from the tree before the hybrid family became the published Zamba2 block,
so a change there that moves a number of the other cells shows here.

Each case serves one job through ``ServeEngine.generate`` (prefill, then
decode through the cache, every token's logits kept) or takes two train
steps, with the seed's weights from ``weights.py``.  XLA's CPU results
depend on how many cores it may use, so the outputs are computed in a
child process held to one core.
"""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
SEEDS = (5, 2**33 + 7)
# case -> seed -> (sha256 of the float32 logits or of the trained
# parameters, the served tokens or the two steps' losses)
PINNED = {
    "yi-9b-12l/serve": {
        5: (
            "da43fd277d2d3be4e4052e2a73207cb5"
            "3f90c3e9ca760e9776db0c378ef20429",
            [[471, 8, 55, 587, 506, 201,
              674, 282, 999, 493, 678, 128],
             [680, 197, 85, 618, 960, 221,
              487, 673, 426, 807, 624, 462]]),
        2**33 + 7: (
            "1c7ad4f06c67ddd48a9b7c922f3b9bd4"
            "75fb72dda946aa52e34038859c07093f",
            [[477, 56, 714, 482, 825, 992,
              337, 852, 700, 668, 558, 790],
             [363, 184, 899, 862, 312, 998,
              46, 353, 787, 854, 338, 705]]),
    },
    "mamba2-130m/serve": {
        5: (
            "7aa8c926162772bcf2e361bfe09d7508"
            "e7a9d538c10b76c5a4b2c5e9f3a586b8",
            [[109, 270, 239, 59, 652, 770,
              445, 120, 569, 421, 987, 898],
             [456, 441, 735, 447, 97, 322,
              212, 368, 853, 124, 22, 463]]),
        2**33 + 7: (
            "2f87f3cb2fac3201cb15f23c84486c57"
            "9fe97a4ea65c965b494d075cfa8c0c2e",
            [[308, 996, 142, 647, 8, 748,
              267, 542, 151, 42, 472, 66],
             [745, 198, 120, 952, 50, 760,
              443, 953, 391, 834, 209, 520]]),
    },
    "mamba2-130m/train": {
        5: (
            "39b6d494b48f40d96483130cb5d6012c"
            "722131b18f9460a6dc0601cea9603c01",
            [7.045248031616211, 7.019227981567383]),
        2**33 + 7: (
            "8c5bc10a7c4b288b9230963cc72700a3"
            "14fb611b8063ade17fb8999ab7c4753b",
            [7.015995025634766, 7.043083190917969]),
    },
}


def _digest(arrays):
    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _config(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


def _serve(name, seed):
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip import harness, traffic, weights
    from repro.configs.base import ModelConfig, RunConfig
    from repro.serving import ServeEngine

    c = _config(name)
    m = dict(c["model"], **harness.family(c["model"]).TINY)
    run = RunConfig(**c["run"]) if c["run"].get("attention_impl") else \
        RunConfig(attention_impl="chunked", attention_chunk=64,
                  remat="none", zero=False)
    params = weights.params_fn(m, jnp.dtype(m["dtype"]))(
        weights.root_key(seed))
    engine = ServeEngine(ModelConfig(**m), params, run=run, batch_size=2,
                         max_len=28)
    prompts = traffic.job_prompts({"batch": 2, "prompt_len": 16},
                                  m["vocab"], seed, 0)
    results = engine.generate(prompts, max_new_tokens=12, return_logits=True)
    return (_digest([np.stack([r.logits for r in results])]),
            [r.tokens for r in results])


def _train(name, seed):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import harness, traffic, weights
    from repro.configs.base import ModelConfig, RunConfig
    from repro.optim import adamw_init
    from repro.train import TrainState, make_train_step

    c = _config(name)
    m = dict(c["model"], **harness.family(c["model"]).TINY)
    params = weights.params_fn(m, jnp.float32)(weights.root_key(seed))
    state = TrainState(params=params, opt=adamw_init(params),
                       step=jnp.zeros((), jnp.int32))
    step = jax.jit(make_train_step(ModelConfig(**m), RunConfig(**c["run"])))
    losses = []
    for i in range(2):
        batch = traffic.train_batch(m["vocab"], 2, 64, seed, i)
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        losses.append(float(metrics["loss"]))
    return _digest(jax.tree.leaves(state.params)), losses


def outputs():
    """Every case's outputs, by case and seed."""
    run = {"serve": _serve, "train": _train}
    return {case: {str(seed): run[case.split("/")[1]](case.split("/")[0],
                                                      seed)
                   for seed in SEEDS}
            for case in PINNED}


@pytest.fixture(scope="module")
def computed():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", sorted(PINNED))
def test_program_outputs_are_pinned(computed, case, seed):
    digest, values = computed[case][str(seed)]
    assert (digest, values) == PINNED[case][seed]


if __name__ == "__main__":
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    print(json.dumps(outputs()))
