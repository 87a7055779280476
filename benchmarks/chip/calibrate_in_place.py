"""``calibrate.py``'s readings with the fault "a decode step that returns its
cache unchanged" planted without a second copy of the cache, for a serve
cell whose cache does not fit twice beside its weights
(``zamba2-serve-offline``).  The benchmark's own runs never run this.

    python3 benchmarks/chip/calibrate_in_place.py --workload <cell> \
        --seeds 1,2,3 [--out <file.jsonl>]

It prints what ``calibrate.py`` prints: per seed the ``gap`` of the
program, of the float8 control and of the stale cache.  Only the fault is
planted another way.  ``calibrate.stale_cache`` copies the whole cache
before each step; here one call, donated the cache, runs the step and then
undoes it: the row the step wrote into each KV stack at the cache's
position is zeroed again, and every other leaf (the SSM and convolution
states, the position) is handed back as it was given, so only those states
are held twice.  A cache the engine makes holds zeros from its position
on (prefill fills the prompt, the growth pads zeros, a step writes at the
position and moves it on), so the cache that comes out equals the cache
that went in, bit for bit.  Reading the rows back from before the step
instead makes the compiler copy both KV stacks (18.26 GiB of a v5e's
15.75 at this cell's size, against 13.26 GiB this way; v5e compile).
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for extra in (ROOT / "src", ROOT):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))

from benchmarks.chip import calibrate  # noqa: E402

KV_STACKS = ("k", "v", "dk", "dv")  # (L, B, T, K, D), written at one position


def stale_cache_in_place(decode):
    """``decode`` with the fault "a decode step that returns its cache
    unchanged", in one call that is donated the cache."""
    import jax
    import jax.numpy as jnp

    def unwrite(stack, pos):
        row = jnp.zeros(stack.shape[:2] + (1,) + stack.shape[3:], stack.dtype)
        return jax.lax.dynamic_update_slice_in_dim(stack, row, pos, axis=2)

    def stale(params, cache, tokens):
        logits, stepped = decode(params, cache, tokens)
        return logits, {
            key: (unwrite(stepped[key], cache["pos"]) if key in KV_STACKS
                  else cache[key])
            for key in cache}
    return jax.jit(stale, donate_argnums=(1,))


if __name__ == "__main__":
    calibrate.stale_cache = stale_cache_in_place
    sys.exit(calibrate.main())
