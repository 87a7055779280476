"""The harness end to end on the CPU, at a tiny size.

Every cell of ``BENCHMARK.json`` loads its configuration and traffic by
name and runs once, in this process, with the chip check steered here (the
harness's ``tpu_chips_on_bus`` and ``PLATFORM``; its compile cache left
off) and the sizes cut to a few layers and a few tokens (the family file's
``TINY`` widths).  The last line it prints has the contract's shape.  Then
the timed path is broken underneath in each way its cell can be broken, and
``correct`` must come out false.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from benchmarks.chip import calibrate, harness, weights
from benchmarks.chip import run as run_mod

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]

# Lengths small enough for the CPU (the widths are each family file's TINY).
TINY_TRAFFIC = {"batch": 2, "prompt_len": 16, "new_tokens": 12,
                "check_requests": 2, "trace_jobs": 1, "seq": 64,
                "trace_steps": 2}
SEED = 2**33 + 7  # wider than 32 bits, as a run's seed may be
LOAD_PEAKS = harness.load_peaks


def tiny_model(m):
    """A model block at its family's CPU sizes."""
    return dict(m, **harness.family(m).TINY)


def tiny(cell):
    cell.config = dict(cell.config, model=tiny_model(cell.config["model"]))
    cell.traffic = dict(cell.traffic, **{
        k: v for k, v in TINY_TRAFFIC.items() if k in cell.traffic})
    return cell


def full_width_logits(monkeypatch):
    """Scale the head's weights so that its logits spread as Yi's do
    (0.02 * sqrt(4096) = 1.28): the cell's limit on the logit gap is in
    those units."""
    spec = weights._leaf_spec

    def scaled(m):
        out = dict(spec(m))
        if ("lm_head",) in out:
            shape, _, copies = out[("lm_head",)]
            std = 0.02 * (4096 / m["d_model"]) ** 0.5
            out[("lm_head",)] = (shape, ("normal", std), copies)
        return out
    monkeypatch.setattr(weights, "_leaf_spec", scaled)


@pytest.fixture
def cpu_harness(monkeypatch):
    full_width_logits(monkeypatch)
    load = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda name: tiny(load(name)))
    monkeypatch.setattr(harness, "tpu_chips_on_bus", lambda: 1)
    monkeypatch.setattr(harness, "PLATFORM", jax.devices()[0].platform)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    kind = jax.devices()[0].device_kind
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    monkeypatch.setattr(harness, "load_peaks",
                        lambda k: peaks if k == kind else LOAD_PEAKS(k))


def run_cell(capsys, cell, trace=0, seconds=1.0):
    rc = run_mod.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       str(seconds), "--trace", str(trace)])
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err


def test_every_cell_names_files_that_exist():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (ROOT / "benchmarks/chip/runners" /
                f"{cell.traffic['kind']}.py").exists()
        assert set(cell.traffic["limits"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (ROOT / "benchmarks/chip/metrics" /
                    f"{m['name']}.py").exists()
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert set(conf["reduced"]) == set(c["reduced"])
        for key in ("assumed", "deployment", "model", "run"):
            assert conf[key] is not None


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_a_contract_line(cpu_harness, capsys, cell, trace):
    rc, line, err = run_cell(capsys, cell, trace)
    assert rc == 0, err
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == jax.devices()[0].platform and dev["count"] == 1
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    entry = harness.load_cell(cell)
    names = {m["name"] for m in (entry.per_layer if trace
                                 else entry.end_to_end)}
    assert set(line["metrics"]) <= names
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == names
    for name, check in line["checks"].items():
        assert check["value"] <= check["limit"]
        assert err.strip().splitlines()[-len(line["checks"]):] \
            and f"check {name}:" in err


def test_no_chip_prints_no_result(monkeypatch, capsys):
    monkeypatch.setattr(harness, "tpu_chips_on_bus", lambda: 0)
    rc, line, err = run_cell(capsys, CELLS[0])
    assert rc == 2 and line is None and "no TPU chip" in err


def test_unknown_device_kind_prints_no_result(cpu_harness, monkeypatch,
                                              capsys):
    monkeypatch.setattr(harness, "load_peaks", LOAD_PEAKS)
    rc, line, err = run_cell(capsys, CELLS[0])
    assert rc == 2 and line is None and "no peaks" in err


def test_alone_in_a_directory_it_prints_no_result(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    subprocess.run(["cp", "-r", str(ROOT / "benchmarks/chip"),
                    str(tmp_path / "benchmarks/chip")], check=True)
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# Faults planted under the timed path ---------------------------------------


def _alter_tokens(monkeypatch):
    from repro.serving import ServeEngine
    generate = ServeEngine.generate

    def altered(self, prompts, **kw):
        results = generate(self, prompts, **kw)
        for r in results:  # one token of every answer, where it is produced
            r.tokens[1] = (r.tokens[1] + 1) % self.cfg.vocab
        return results
    monkeypatch.setattr(ServeEngine, "generate", altered)


def _drop_half_the_requests(monkeypatch):
    from repro.serving import ServeEngine
    generate = ServeEngine.generate

    def half(self, prompts, **kw):
        results = generate(self, prompts, **kw)
        for r in results[len(results) // 2:]:
            r.tokens = []
        return results
    monkeypatch.setattr(ServeEngine, "generate", half)


def _decode_keeps_its_cache(monkeypatch):
    from repro.serving import ServeEngine
    init = ServeEngine.__init__

    def frozen(self, *a, **kw):
        init(self, *a, **kw)
        self.decode = calibrate.stale_cache(self.decode)
    monkeypatch.setattr(ServeEngine, "__init__", frozen)


def _step_keeps_its_state(monkeypatch):
    import repro.launch.train as launch

    jit_train_step = launch.jit_train_step

    def unchanged(*a, **kw):
        step_fn, shardings, data = jit_train_step(*a, **kw)
        return jax.jit(lambda s, b: (s, step_fn(s, b)[1])), shardings, data
    monkeypatch.setattr(launch, "jit_train_step", unchanged)


def _step_sees_half_the_batch(monkeypatch):
    import repro.launch.train as launch
    from repro.train import make_train_step

    jit_train_step = launch.jit_train_step

    def half(cfg, run, *a, **kw):
        _, shardings, data = jit_train_step(cfg, run, *a, **kw)
        step = make_train_step(cfg, run)

        def step_half(s, b):
            n = b["tokens"].shape[0] // 2
            return step(s, {k: v[:n] for k, v in b.items()})
        return jax.jit(step_half), shardings, data
    monkeypatch.setattr(launch, "jit_train_step", half)


FAULTS = {
    "serve_offline": [_alter_tokens, _drop_half_the_requests,
                      _decode_keeps_its_cache],
    "train": [_step_keeps_its_state, _step_sees_half_the_batch],
}
CASES = [(cell, fault) for cell in CELLS
         for fault in FAULTS.get(harness.load_cell(cell).traffic["kind"],
                                 ())]


@pytest.mark.parametrize(
    "cell,fault", CASES,
    ids=[f"{c}-{f.__name__.strip('_')}" for c, f in CASES])
def test_a_broken_timed_path_is_not_correct(cpu_harness, monkeypatch, capsys,
                                            cell, fault):
    fault(monkeypatch)
    rc, line, err = run_cell(capsys, cell)
    assert rc == 0, err
    assert line["correct"] is False, line["checks"]
