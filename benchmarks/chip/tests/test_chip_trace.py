"""The reduction from a profiler trace to numbers (``trace.py``), on a small
trace recorded on a TPU v5e host of four chips (``data/probe_2x2.xplane.pb``:
four runs each of a program whose contraction is sharded over the chips, so
it ends in an all-reduce, and of an elementwise program, with the host
sleeping inside a ``data.next`` span between them), and on hand-made
intervals."""

import copy
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip import harness, trace

CHIP = Path(__file__).resolve().parents[1]
PROBE = str(Path(__file__).resolve().parent / "data" / "probe_2x2.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    tr = trace.load(PROBE, spans=("step", "data.next"))
    return tr, trace.reduce(tr)


def test_union_counts_overlap_once():
    ivs = [(0, 10), (5, 15), (20, 30), (25, 26)]
    assert trace.union(ivs) == [(0, 15), (20, 30)]
    assert trace.length(trace.union(ivs)) == 25  # the sum would be 36
    assert trace.subtract([(0, 30)], trace.union(ivs)) == [(15, 20)]
    assert trace.clip([(0, 15), (20, 30)], (10, 25)) == [(10, 15), (20, 25)]


def test_busy_is_the_union_of_operations(probe):
    tr, red = probe
    assert sorted(red.busy_s) == [0, 1, 2, 3]
    for idx, dev in tr.devices.items():
        ops = [iv for _, _, iv in dev.ops]
        union = trace.length(trace.union(ops)) * 1e-9
        summed = sum(e - s for s, e in ops) * 1e-9
        assert red.busy_s[idx] == pytest.approx(union)
        assert red.busy_s[idx] <= summed + 1e-12
        assert 0 < red.busy_s[idx] < red.window_s


def test_time_per_module(probe):
    tr, red = probe
    assert red.module_calls == {"jit_probe_step": 4, "jit_probe_other": 4}
    assert red.module_s["jit_probe_step"] > red.module_s["jit_probe_other"]
    per_device = sum(e - s for _, (s, e) in tr.devices[0].modules) * 1e-9
    total = sum(red.module_s.values())
    assert total == pytest.approx(4 * per_device, rel=0.05)
    assert all(op.split("/")[0] in red.module_s for op in red.op_s)


def test_collective_time_and_its_exposed_part(probe):
    _, red = probe
    for idx in range(4):
        # The all-reduce runs alone on each chip: all of it is exposed.
        assert red.collective_s[idx] > 0
        assert red.collective_exposed_s[idx] == pytest.approx(
            red.collective_s[idx])
    # Compute overlapping a collective hides that part of it.
    ops = [("m", "all-gather.3", (10, 50)), ("m", "fusion.1", (30, 60)),
           ("m", "all-reduce-start", (70, 80))]
    tr = trace.Trace(window=(0, 100), host_spans=[],
                     devices={0: trace.DeviceTrace(ops=ops)})
    red = trace.reduce(tr)
    assert red.collective_s[0] == pytest.approx(50e-9)
    assert red.collective_exposed_s[0] == pytest.approx(30e-9)
    assert red.busy_s[0] == pytest.approx(60e-9)  # the sum would be 90


def test_idle_gaps_are_labelled_by_host_spans(probe):
    _, red = probe
    idle = red.window_s - red.busy_s[min(red.busy_s)]
    assert sum(sec for _, sec in red.gaps) == pytest.approx(idle)
    longest = red.gaps[0]
    assert longest[0] == "data.next" and longest[1] > 3e-3  # 4 ms sleeps
    out = trace.breakdown(red)
    assert out["idle_gaps"][0][0] == "data.next"
    assert out["device_ops"][0][0] == "jit_probe_step/all-reduce"
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), CHIP / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_reader_finds_nothing_without_its_module(probe):
    red = copy.deepcopy(probe[1])
    read = _reader("decode_hbm_roofline.serve")
    model = {"family": "dense", "n_layers": 1, "d_model": 8, "n_heads": 2,
             "n_kv_heads": 1, "d_head": 4, "d_ff": 16, "vocab": 30}
    traffic = {"batch": 2, "prompt_len": 4, "new_tokens": 3}
    reading = harness.Reading(red, {"decode_calls": 4}, model, traffic,
                              {"hbm_bytes_per_s": 819e9}, 4)
    assert read(reading) is None  # no decode module in this trace
    red.module_calls["jit__lambda"] = 3
    red.module_s["jit__lambda"] = 1e-3
    assert read(reading) is None  # a module run another number of times
    red.module_calls["jit__lambda"] = 4
    assert read(reading) > 0
