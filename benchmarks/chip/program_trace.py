"""Split one cell's traced window by the names the program gives its work.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n>

Sets the cell up and traces its window as ``run.py --trace 1`` does, with
the runner's own functions (one process, one TPU; exits 2 without one),
then reads that trace by the program's own names and prints one JSON
object as the last line of standard output:

- ``compiles``: the program's compile events inside the window
  (``repro.launch.compile_cache.compile_counts`` before and after it),
  ``total`` and by ``"<trace|compile>:<function>"``;
- serve cells, ``idle``: the window, the device's idle share of it (as
  ``idle_share.serve`` reads it) and, per innermost program span
  (``SERVE_SPANS``, and ``none``), the idle seconds and their share of the
  window (``serve_split``);
- train cells, ``scopes``: the compiled step's leaf-operation seconds under
  ``TRAIN_SCOPES`` and ``other``, each as a share of them all, and the
  share that the compiled text does not name (``train_split``).

Nothing is checked against the reference, and no metric of
``BENCHMARK.json`` reads these numbers: they are for a person reading where
the time goes.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# As in run.py: siblings as ``benchmarks.chip.<name>``, the program from src.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for extra in (ROOT / "src", ROOT):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))

from benchmarks.chip import harness, scopes, trace  # noqa: E402
from benchmarks.chip.harness import NoChip  # noqa: E402

# The program's host spans (``repro.serving.engine``) and named scopes
# (``repro.models.mamba2.ssd_chunked``, ``repro.train.step._loss_fn``).
SERVE_SPANS = ("serve.wave", "serve.prefill", "serve.grow_cache",
               "serve.sample", "serve.decode")
TRAIN_SCOPES = ("ssd", "head_loss")


def _share(part: float, whole: float):
    return 100.0 * part / whole if whole else None


def serve_split(path: str) -> dict:
    """Idle time of the first device in the traced window by innermost
    program span, beside the window's whole idle share."""
    tr = trace.load(path, spans=SERVE_SPANS)
    window_s = (tr.window[1] - tr.window[0]) * 1e-9
    red = trace.reduce(tr)
    idle = scopes.idle_by_span(tr)
    return {"window_s": window_s,
            "idle_share": _share(window_s - red.busy_mean_s, window_s)
            if red.busy_s else None,
            "idle_s": idle,
            "idle_share_by_span": {k: _share(v, window_s)
                                   for k, v in idle.items()}}


def train_split(path: str, text: str) -> dict:
    """The compiled step's leaf-operation time by named scope."""
    ops = scopes.op_map([text])
    module = scopes.module_of(text)
    got = scopes.scope_time(trace.load(path, spans=()), ops, module,
                            TRAIN_SCOPES)
    return {"module": module, "leaf_s": got.leaf_s,
            "seconds": got.seconds,
            "share": {k: _share(v, got.leaf_s)
                      for k, v in got.seconds.items()},
            "unmapped_share": _share(got.unmapped_s, got.leaf_s)}


def _counts() -> dict:
    from repro.launch.compile_cache import compile_counts
    return compile_counts()


def _compiles(before: dict, after: dict) -> dict:
    grew = {f"{kind}:{fun}": n - before.get((kind, fun), 0)
            for (kind, fun), n in after.items()
            if n != before.get((kind, fun), 0)}
    return {"total": sum(grew.values()), "by_function": grew}


def traced_serve(ctx) -> dict:
    from benchmarks.chip.runners import serve_offline as runner

    t, vocab = ctx.traffic, ctx.model["vocab"]
    _, _, engine = runner.build(ctx)
    runner.serve(engine, t, vocab, ctx.seed, runner.WARM_JOB)
    captured = []
    try:
        before = _counts()
        with trace.capture() as captured:
            for j in range(t["trace_jobs"]):
                runner.serve(engine, t, vocab, ctx.seed, j)
        after = _counts()
        return {"compiles": _compiles(before, after),
                "idle": serve_split(captured[-1])}
    finally:
        trace.discard(captured)


def traced_train(ctx) -> dict:
    from benchmarks.chip.runners import train as runner
    from repro.distributed import set_mesh_context

    root, params_fn, step_fn, state, pipeline = runner.build(ctx)
    captured = []
    try:
        compiled, state, _ = runner.first_steps(
            ctx.traffic, root, params_fn, step_fn, state, pipeline)
        before = _counts()
        with trace.capture() as captured:
            for _ in range(ctx.traffic["trace_steps"]):
                state, _ = runner.step_once(compiled, state, pipeline)
        after = _counts()
        return {"compiles": _compiles(before, after),
                "scopes": train_split(captured[-1], compiled.as_text())}
    finally:
        trace.discard(captured)
        pipeline.close()
        set_mesh_context(None)


TRACED = {"serve_offline": traced_serve, "train": traced_train}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.start_jax(cell.entry["chips"])
        peak = harness.load_peaks(devices[0].device_kind)
    except NoChip as exc:
        print(f"program_trace: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    ctx = harness.Context(cell, args.seed, 0.0, True, devices, peak, T_START)
    out = {"workload": cell.name, "seed": args.seed,
           **TRACED[cell.traffic["kind"]](ctx)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
