"""Model families: all the harness knows of one is its family file,
``reference/<family>.py``, found by the configuration's ``model.family``.

The counts, weights and reference values of the benchmark's configurations
are pinned to what they were while ``weights.py`` and ``flops.py`` still
branched on the family: a change of any of them moves the benchmark."""

import ast
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops, harness, traffic, weights
from test_chip_rehearsal import cpu_harness, run_cell, tiny_model  # noqa: F401

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# The program's families (``ModelConfig.family``).
PROGRAM_FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm")
SHARED = ["weights.py", "flops.py", "harness.py", "calibrate.py", "run.py",
          "traffic.py", "reference/train.py", "reference/numerics.py"] + \
    sorted(f"runners/{p.name}" for p in (CHIP / "runners").glob("*.py"))

# Sizes the counts are pinned at: the serve cell's job (batch, prompt, new
# tokens), and the train cell's batch and sequence with 256 new tokens.
SIZES = {"yi-9b-12l": (64, 1024, 256), "mamba2-130m": (16, 2048, 256)}
COUNTS = {
    "yi-9b-12l": {
        "layer_params": 173023232, "param_count": 2600570880,
        "prefill_flops": 278766194524160.0, "decode_flops": 312203018240.0,
        "decode_bytes": 6289563648.0, "serve_job_flops": 358785461780480.0,
        "serve_job_decode_bytes": 6489317376.0,
        "train_flops_per_token": 14332231680.0},
    "mamba2-130m": {
        "layer_params": 3763528, "param_count": 128946624,
        "prefill_flops": 7205506646016.0, "decode_flops": 4118937600.0,
        "decode_bytes": "raises", "serve_job_flops": 8255835734016.0,
        "serve_job_decode_bytes": "raises",
        "train_flops_per_token": 891297792.0},
}
# sha256 of the float32 parameter tree at the rehearsal's sizes, by seed.
DIGESTS = {
    "yi-9b-12l": {
        5: "88a25e7371c05f1fe2f61bf78228166c8c22500c1ce248a52c806954b66fc45f",
        2**33 + 7: "3eddd088afd2af2070b40d6c1235464f"
                   "654b6f16f3b9701db1f6c6cc17ff3da6"},
    "mamba2-130m": {
        5: "1a67ddc41cdef0bc07b09cc5308cd743e19a6ea2d9e2b94aadce08fdd589d398",
        2**33 + 7: "fb538444d25119558d35004479742dac"
                   "d8e87e6624c76fb8fe6347db618697b7"},
}
# The float32 reference at the rehearsal's sizes (the serve reference's
# logits at positions 8-15 of two rows; the train reference's loss): the
# sum of the finite values' magnitudes, the first and the last.
REFERENCE = {
    "yi-9b-12l": {
        5: (5766.162376208464, 0.5090505480766296, -0.21992653608322144),
        2**33 + 7: (5942.503550348338, -0.7093925476074219,
                    -0.604841947555542)},
    "mamba2-130m": {
        5: (7.045248508453369,) * 3,
        2**33 + 7: (7.015995025634766,) * 3},
}


def _model(config):
    entry = {c["name"]: c for c in BENCH["configs"]}[config]
    return json.loads((ROOT / entry["file"]).read_text())["model"]


def _counts(m, batch, prompt, new):
    calls = {
        "layer_params": lambda: flops.layer_params(m),
        "param_count": lambda: flops.param_count(m),
        "prefill_flops": lambda: flops.prefill_flops(m, batch, prompt),
        "decode_flops": lambda: flops.decode_flops(m, batch, prompt + 1),
        "decode_bytes": lambda: flops.decode_bytes(m, batch, prompt + 1),
        "serve_job_flops": lambda: flops.serve_job_flops(
            m, batch, prompt, new),
        "serve_job_decode_bytes": lambda: flops.serve_job_decode_bytes(
            m, batch, prompt, new),
        "train_flops_per_token": lambda: flops.train_flops_per_token(
            m, prompt),
    }
    out = {}
    for name, call in calls.items():
        try:
            out[name] = call()
        except ValueError:
            out[name] = "raises"
    return out


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _reference(config, m, seed):
    root = weights.root_key(seed)
    batch = traffic.train_batch(m["vocab"], 2, 64, seed, 0)
    family = harness.family(m)
    if config == "yi-9b-12l":
        out = family.logits(m, root, batch["tokens"][:, :16], 8,
                            jnp.bfloat16)["f32"]
    else:
        params = weights.params_fn(m, jnp.float32)(root)
        out = family.loss(params, batch["tokens"], batch["labels"], m)
    a = np.asarray(out)
    finite = a[np.isfinite(a)].astype(np.float64)
    return float(np.abs(finite).sum()), float(finite[0]), float(finite[-1])


@pytest.mark.parametrize("seed", [5, 2**33 + 7])
@pytest.mark.parametrize("config", sorted(SIZES))
def test_counts_weights_and_reference_are_pinned(config, seed):
    m = _model(config)
    assert _counts(m, *SIZES[config]) == COUNTS[config]
    t = tiny_model(m)
    params = weights.params_fn(t, jnp.float32)(weights.root_key(seed))
    assert _digest(params) == DIGESTS[config][seed]
    # float32 products at HIGHEST; only the CPU's summation order may differ
    np.testing.assert_allclose(_reference(config, t, seed),
                               REFERENCE[config][seed], rtol=1e-5)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_resolves_to_a_family_file(config):
    m = _model(config)
    family = harness.family(m)
    assert Path(family.__file__) == CHIP / "reference" / f"{m['family']}.py"
    for name in ("leaves", "stack_params", "matmul_params", "mixer_flops",
                 "decode_mixer_flops", "decode_state_bytes", "TINY"):
        assert hasattr(family, name), name
    assert set(family.TINY) <= set(m)


CALL_COUNTER = '''

CALLS = []
_logits = logits


def logits(*args, **kwargs):
    CALLS.append(args[2].shape)
    return _logits(*args, **kwargs)
'''


def test_a_family_file_is_found_by_name(cpu_harness, monkeypatch, capsys,
                                        tmp_path):
    text = (CHIP / "reference" / "dense.py").read_text()
    (tmp_path / "dense.py").write_text(text + CALL_COUNTER)
    monkeypatch.setattr(harness, "FAMILIES", tmp_path)
    rc, line, err = run_cell(capsys, "yi9b-serve-offline")
    assert rc == 0, err
    assert line["correct"] is True, line["checks"]
    family = harness.family({"family": "dense"})
    assert Path(family.__file__).parent == tmp_path
    assert family.CALLS  # the served tokens went through this file's logits


def test_a_family_without_a_file_names_it():
    m = dict(_model("yi-9b-12l"), family="no_such_family")
    for call in (harness.family, flops.param_count,
                 lambda m: weights.params_fn(m, jnp.float32)(
                     weights.root_key(0))):
        with pytest.raises(FileNotFoundError,
                           match=r"reference/no_such_family\.py"):
            call(m)


GROUPED = '''
import jax
import jax.numpy as jnp

from benchmarks.chip.weights import Leaf


def _two_to_three(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, 2.0, 3.0)


def leaves(m):
    d = m["d_model"]
    return {
        ("layers", "w"): Leaf((d, d), ("normal", 0.02)),
        ("groups", "w"): Leaf((d,), _two_to_three, copies=3),
        ("shared", "w"): Leaf((d, 2), ("ones",), copies=0),
    }
'''


def test_a_family_stacks_by_its_own_count_and_draws_by_its_own_law(
        monkeypatch, tmp_path):
    (tmp_path / "grouped.py").write_text(GROUPED)
    monkeypatch.setattr(harness, "FAMILIES", tmp_path)
    m = {"family": "grouped", "n_layers": 2, "d_model": 4, "vocab": 10,
         "tie_embeddings": True}
    root = weights.root_key(2**33 + 1)
    p = weights.params_fn(m, jnp.float32)(root)
    assert set(p) == {"embed", "final_norm", "layers", "groups", "shared"}
    assert p["layers"]["w"].shape == (2, 4, 4)
    assert p["groups"]["w"].shape == (3, 4)
    assert bool(jnp.all((p["groups"]["w"] >= 2) & (p["groups"]["w"] <= 3)))
    assert p["shared"]["w"].shape == (4, 2)
    for c in range(3):
        np.testing.assert_array_equal(
            weights.layer_params(m, root, c, jnp.float32, "groups")["w"],
            p["groups"]["w"][c])
    np.testing.assert_array_equal(
        weights.layer_params(m, root, 1, jnp.float32)["w"],
        p["layers"]["w"][1])
    top = weights.top_params(m, root, jnp.float32)
    assert set(top) == {"embed", "final_norm", "shared"}
    np.testing.assert_array_equal(top["shared"]["w"], p["shared"]["w"])


def _names_a_family(path, families):
    tree = ast.parse(path.read_text())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and node.value in families:
            found.append(node.value)
        elif isinstance(node, ast.Import):
            found += [a.name for a in node.names
                      if a.name.startswith("benchmarks.chip.reference.")
                      and a.name.rsplit(".", 1)[1] in families]
        elif isinstance(node, ast.ImportFrom) and node.module:
            parts = node.module.split(".")
            if node.module == "benchmarks.chip.reference":
                found += [a.name for a in node.names if a.name in families]
            elif node.module.startswith("benchmarks.chip.reference.") \
                    and parts[-1] in families:
                found.append(node.module)
    return found


@pytest.mark.parametrize("shared", SHARED)
def test_no_shared_file_names_a_family(shared):
    families = set(PROGRAM_FAMILIES) | {
        p.stem for p in (CHIP / "reference").glob("*.py")} - {
        Path(s).stem for s in SHARED}
    assert _names_a_family(CHIP / shared, families) == []
