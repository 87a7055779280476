"""Run one benchmark cell once on the TPU, and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration (``configs/<config>.json``), its model family's
file (``reference/<family>.py``, by the configuration's ``model.family``:
the family's parameter leaves, counts, plain reference and CPU sizes), its
traffic (``traffic/<cell>.json``, whose ``kind`` names the runner in
``runners/``) and its per-layer metrics (``metrics/<metric>.py``) are found
by name.  So a new configuration adds, and edits no file that is there:

- ``configs/<config>.json``, and its entry under ``configs``;
- ``reference/<family>.py``, where its family has no file yet;
- for each of its cells ``traffic/<cell>.json``, and an entry under
  ``workloads``;
- for each new per-layer metric ``metrics/<metric>.py``, and an entry under
  ``per_layer``.

One process, on the machine it is started on: it finds the TPU on the PCI
bus before any backend starts and asks JAX for it whatever
``JAX_PLATFORMS`` says; with no TPU, too few chips, or a device kind that
``peaks.json`` does not hold, it exits 2 and prints no result.  It builds
the weights and inputs from ``--seed``, warms up the cell's own shapes
(set-up), measures for ``--seconds`` (``--trace 0``: the end-to-end
metrics) or traces a short window (``--trace 1``: the per-layer metrics
from the profiler trace), then checks what the timed path produced
against the plain reference and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared beside its limit (also the last lines of
standard error).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Sibling modules are imported as ``benchmarks.chip.<name>`` (``trace`` would
# otherwise shadow the standard library's), the program from ``src``.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for extra in (ROOT / "src", ROOT):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.harness import NoChip  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = harness.load_cell(args.workload)
        devices = harness.start_jax(cell.entry["chips"])
        peak = harness.load_peaks(devices[0].device_kind)
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    runner = importlib.import_module(
        f"benchmarks.chip.runners.{cell.traffic['kind']}")
    ctx = harness.Context(cell, args.seed, args.seconds, bool(args.trace),
                          devices, peak, T_START)
    out = runner.run(ctx)
    line = harness.result_line(ctx, out)
    print(f"window and checks: setup {out.setup_s:.3f} s, reference "
          f"{out.reference_s:.3f} s", file=sys.stderr)
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
