"""Record the small trace that ``test_chip_scopes.py`` reads.

    python3 benchmarks/chip/tests/record_scopes_probe.py   # on one TPU v5e

The probe is a jitted gradient step (``jit_probe_step``) whose forward runs
a ``lax.scan`` (a ``while`` on the device) under the named scope ``ssd`` and
a matrix product and log-sum-exp under ``head_loss``, rematerialised, so
that both scopes appear in the forward, backward and recomputed passes.  It
runs three times inside a traced window, each call inside a host span
``step``.  Written beside this file: ``data/probe_scopes_1x1.xplane.pb``
and the compiled program's text, ``data/probe_scopes_1x1.hlo.txt``.
"""

import argparse
import glob
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from benchmarks.chip import trace  # noqa: E402

STEM = HERE / "data" / "probe_scopes_1x1"


def loss(w, x):
    with jax.named_scope("ssd"):
        def body(h, xt):
            h = 0.9 * h + jnp.tanh(xt)
            return h, h
        _, hs = jax.lax.scan(body, jnp.zeros_like(x[0]), x)
    with jax.named_scope("head_loss"):
        logits = hs @ w
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))


def probe_step(w, x):
    return jax.value_and_grad(jax.checkpoint(loss))(w, x)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scan", type=int, default=16, help="scan length")
    ap.add_argument("--out", type=Path, default=STEM,
                    help="path of the two files, less their suffixes")
    args = ap.parse_args()
    stem = args.out
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 2048), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (args.scan, 512, 256),
                          jnp.float32)
    step = jax.jit(probe_step)
    compiled = step.lower(w, x).compile()
    jax.block_until_ready(compiled(w, x))
    # The benchmark's ``trace.capture``, with the host's own events and the
    # programs' HLO left out of the file to keep it small.
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    tdir = tempfile.mkdtemp(prefix="scopes-probe-")
    try:
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            with TraceAnnotation(trace.WINDOW_SPAN):
                for _ in range(3):
                    with TraceAnnotation("step"):
                        jax.block_until_ready(compiled(w, x))
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        shutil.copy(found[0], stem.with_suffix(".xplane.pb"))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    stem.with_suffix(".hlo.txt").write_text(compiled.as_text())
    for suffix in (".xplane.pb", ".hlo.txt"):
        path = stem.with_suffix(suffix)
        print(path.name, path.stat().st_size, "bytes")


if __name__ == "__main__":
    main()
