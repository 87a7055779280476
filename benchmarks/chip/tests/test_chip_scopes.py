"""Device time by named scope and idle time by innermost host span
(``scopes.py``): on ``record_scopes_probe.py``'s probe (a rematerialised
gradient step whose scopes ``ssd``, a ``lax.scan`` and so a ``while``, and
``head_loss`` appear in its forward, backward and recomputed passes) as
recorded on one TPU v5e and as compiled here for the CPU, and on hand-made
intervals; ``program_trace.py`` end to end on the CPU at a tiny size; and
the reader of ``prefill_mfu.serve``."""

import importlib.util
import json
from pathlib import Path

import pytest

from benchmarks.chip import harness, program_trace, scopes, trace
from benchmarks.chip.tests.test_chip_rehearsal import (  # noqa: F401
    SEED, cpu_harness)

CHIP = Path(__file__).resolve().parents[1]
PROBE = CHIP / "tests" / "data" / "probe_scopes_1x1"
MODULE = "jit_probe_step"
SCOPES = ("ssd", "head_loss")


def test_scope_path_strips_transform_wrappers():
    assert scopes.scope_path(
        "jit(train_step)/transpose(jvp(ssd))/while/body/mul") == (
        "train_step", "ssd", "while", "body", "mul")
    assert scopes.scope_path("jit(f)/transpose(jvp())/checkpoint/ssd") == (
        "f", "checkpoint", "ssd")


@pytest.fixture(scope="module")
def cpu_text():
    """The probe compiled here, at a small size, for the CPU."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.tests import record_scopes_probe as probe_mod
    return jax.jit(probe_mod.probe_step).lower(
        jnp.ones((8, 16)), jnp.ones((4, 2, 8))).compile().as_text()


def test_compiled_text_gives_opcodes_and_scopes(cpu_text):
    ops = scopes.op_map([cpu_text])
    assert scopes.module_of(cpu_text) == MODULE
    mine = {op: v for (mod, op), v in ops.items() if mod == MODULE}
    assert {"while", "fusion"} <= {opcode for opcode, _ in mine.values()}
    assert set(SCOPES) <= scopes.scopes_in(ops, MODULE)


@pytest.fixture(scope="module")
def recorded():
    """The probe's trace on one TPU v5e and its compiled text there."""
    tr = trace.load(str(PROBE.with_suffix(".xplane.pb")), spans=("step",))
    return tr, scopes.op_map([PROBE.with_suffix(".hlo.txt").read_text()])


def test_every_recorded_leaf_op_maps_to_a_scope_or_other(recorded):
    tr, ops = recorded
    assert scopes.module_of(PROBE.with_suffix(".hlo.txt").read_text()) == \
        MODULE
    ran = {op for dev in tr.devices.values() for mod, op, _ in dev.ops
           if mod == MODULE}
    assert ran and ran <= {op for mod, op in ops if mod == MODULE}
    assert any(ops[(MODULE, op)][0] == "while" for op in ran)
    got = scopes.scope_time(tr, ops, MODULE, SCOPES)
    assert got.unmapped_s == 0.0 and got.leaf_s > 0
    assert got.seconds["ssd"] > 0 and got.seconds["head_loss"] > 0
    assert sum(got.seconds.values()) == pytest.approx(got.leaf_s)


def test_recorded_scope_time_is_at_most_busy_time(recorded):
    tr, ops = recorded
    red = trace.reduce(tr)
    got = scopes.scope_time(tr, ops, MODULE, SCOPES)
    # Leaf ops do not overlap on a core, so their sum stays within the
    # union of every op's interval; a ``while`` counted too would not.
    busy = sum(red.busy_s.values())
    assert got.leaf_s <= busy * (1 + 1e-9)
    whiles = sum(v for k, v in red.op_s.items()
                 if k.startswith(f"{MODULE}/while"))
    assert got.leaf_s + whiles > busy


def test_recorded_idle_is_split_by_span_and_sums_to_the_idle(recorded):
    tr, _ = recorded
    idle = scopes.idle_by_span(tr)
    red = trace.reduce(tr)
    assert set(idle) == {"step", scopes.NONE}
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s[min(red.busy_s)])


def test_scope_time_counts_leaf_ops_by_innermost_scope(cpu_text):
    ops = scopes.op_map([cpu_text])
    by_scope = {}
    for (mod, op), (opcode, path) in ops.items():
        kind = "while" if opcode == "while" else next(
            (s for s in reversed(path) if s in SCOPES), scopes.OTHER)
        by_scope.setdefault(kind, op)
    # One op of each kind, 10 ns each, and a ``while`` over all of them; an
    # op the text does not name, and one of another module.
    names = [by_scope[k] for k in ("ssd", "head_loss", scopes.OTHER)]
    run = [(MODULE, by_scope["while"], (0, 40))]
    run += [(MODULE, op, (10 * i, 10 * i + 10)) for i, op in enumerate(names)]
    run += [(MODULE, "fusion.99999", (30, 40)), ("jit_other", names[0],
                                                  (40, 50))]
    tr = trace.Trace(window=(0, 50), host_spans=[],
                     devices={0: trace.DeviceTrace(ops=run)})
    got = scopes.scope_time(tr, ops, MODULE, SCOPES)
    assert {k: round(v * 1e9) for k, v in got.seconds.items()} == {
        "ssd": 10, "head_loss": 10, scopes.OTHER: 10}
    assert round(got.unmapped_s * 1e9) == 10
    assert round(got.leaf_s * 1e9) == 40


def test_idle_goes_to_the_innermost_span():
    ops = [("m", "fusion.1", (10, 20)), ("m", "fusion.2", (30, 50)),
           ("m", "fusion.3", (60, 90)), ("m", "fusion.4", (95, 100))]
    spans = [("serve.wave", (0, 92)), ("serve.sample", (18, 32)),
             ("serve.decode", (55, 58)), ("serve.sample", (58, 64))]
    tr = trace.Trace(window=(0, 110), host_spans=spans,
                     devices={0: trace.DeviceTrace(ops=ops)})
    got = {k: round(v * 1e9) for k, v in scopes.idle_by_span(tr).items()}
    # (0, 10) wave alone; (20, 30) inside both: the shorter, sample;
    # (50, 60) overlaps wave 10, decode 3, sample 2: the wave; (90, 95)
    # overlaps the wave most; (100, 110) no span.
    assert got == {"serve.wave": 10 + 10 + 5, "serve.sample": 10,
                   "serve.decode": 0, "none": 10}
    assert scopes.innermost([("a", (0, 10)), ("b", (0, 10))], (2, 4)) == "a"
    assert scopes.innermost([], (2, 4)) == scopes.NONE


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), CHIP / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


MODEL = {"family": "dense", "n_layers": 1, "d_model": 8, "n_heads": 2,
         "n_kv_heads": 1, "d_head": 4, "d_ff": 16, "vocab": 30}
TRAFFIC = {"batch": 2, "prompt_len": 4, "new_tokens": 3}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reading(red, counters):
    return harness.Reading(red, counters, MODEL, TRAFFIC, PEAK, 1)


def _reduced():
    """A window of 1 ms with one program run on one device."""
    ops = [("jit_step", "fusion.1", (0, 400_000))]
    return trace.reduce(trace.Trace(
        window=(0, 1_000_000), host_spans=[],
        devices={0: trace.DeviceTrace(
            ops=ops, modules=[("jit_step", (0, 400_000))])}))


def test_prefill_reader_finds_nothing_without_its_input():
    read = _reader("prefill_mfu.serve")
    assert read(_reading(None, {"jobs": 3})) is None
    red = _reduced()  # no prefill module
    assert read(_reading(red, {"jobs": 3})) is None
    red.module_calls["jit_prefill"] = 2  # not one a job
    red.module_s["jit_prefill"] = 2e-6
    assert read(_reading(red, {"jobs": 3})) is None
    assert read(_reading(red, {})) is None


def test_prefill_reader_reads_its_module():
    red = _reduced()
    red.module_calls["jit_prefill"] = 3  # one prefill a job
    red.module_s["jit_prefill"] = 3e-6
    assert _reader("prefill_mfu.serve")(_reading(red, {"jobs": 3})) == \
        pytest.approx(100 * 10880 / 1e-6 / 197e12)


@pytest.mark.parametrize("cell, key", [("yi9b-serve-offline", "idle"),
                                       ("mamba2-train-2k", "scopes")])
def test_program_trace_prints_its_split(cpu_harness, capsys, cell, key):
    assert program_trace.main(["--workload", cell, "--seed",
                               str(SEED)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["workload"] == cell and line["seed"] == SEED
    assert line["compiles"] == {"total": 0, "by_function": {}}
    if key == "idle":  # the CPU has no device plane: spans, and no time
        assert set(line["idle"]["idle_s"]) == {
            *program_trace.SERVE_SPANS, scopes.NONE}
        assert line["idle"]["window_s"] > 0
    else:
        assert line["scopes"]["module"] == "jit_train_step"
        assert set(line["scopes"]["seconds"]) == {
            *program_trace.TRAIN_SCOPES, scopes.OTHER}


def test_program_trace_exits_2_without_a_chip(monkeypatch, capsys):
    monkeypatch.setattr(harness, "tpu_chips_on_bus", lambda: 0)
    assert program_trace.main(["--workload", "mamba2-train-2k", "--seed",
                               "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
