"""The control and the stale-cache fault of ``zamba2-serve-offline``, at a
size a test run holds.

As ``test_chip_control.py`` does for the dense cell: ``calibrate.py``'s
readings of the program, of the reference computed in float8 and of a
decode that returns its cache unchanged, over a few seeds, here at a
reduced width (both shared blocks twice, two groups of B and C).  The
control and the fault must read far above the program's gap, and the
fault above the cell's limit.  At this width and depth the float8
control's rounding passes through fewer and narrower layers than at the
cell's size (it reads 0.2-0.5 here, against the program's 0-0.012), so it
is held to ten times the program's gap and to ten times 0.01, not to the
limit; ``calibrate.py`` reads it at full size on the chip (PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import calibrate, calibrate_in_place, harness

SEEDS = [3, 2**32 + 5, 11]


def _context():
    c = harness.load_cell("zamba2-serve-offline")
    c.config = dict(c.config, model=dict(
        c.config["model"], n_layers=7, hybrid_pattern="MHHMHHM",
        d_model=512, n_heads=4, n_kv_heads=4, d_head=256, d_ff=1024,
        vocab=4000, ssm_state=32, ssm_head_dim=64, adapter_rank=16))
    c.traffic = dict(c.traffic, batch=2, prompt_len=32, new_tokens=16,
                     check_requests=2)
    return harness.Context(c, 0, 0.0, False, jax.devices()[:1], {}, 0.0)


def test_serve_control_and_stale_cache_read_far_above_the_program():
    ctx = _context()
    limit = ctx.traffic["limits"]["gap"]
    for r in calibrate.serve_readings(ctx, SEEDS):
        assert r["program"]["gap"] <= limit / 5, r
        assert r["control"]["gap"] >= 10 * max(r["program"]["gap"], 0.01), r
        assert r["stale_cache"]["gap"] > limit, r


def test_in_place_fault_hands_back_the_cache_it_was_given():
    """``calibrate_in_place``'s fault, though decode is donated its cache:
    the step's logits, and the cache it was given, bit for bit."""
    from benchmarks.chip.runners import serve_offline as drv

    ctx = _context()
    ctx.seed = SEEDS[0]
    _, _, engine = drv.build(ctx)
    logits, cache = engine.prefill_wave([[1] * 8, [2] * 8], max_new_tokens=4)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    given = jax.tree.map(np.asarray, cache)
    stale = calibrate_in_place.stale_cache_in_place(engine.decode)
    got_logits, got = stale(engine.params, cache, tok)
    want_logits, _ = engine.decode(engine.params,
                                   jax.tree.map(jnp.asarray, given), tok)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    assert got.keys() == given.keys()
    for key in given:
        np.testing.assert_array_equal(np.asarray(got[key]), given[key],
                                      err_msg=key)


def test_in_place_fault_reads_what_the_copying_fault_reads(monkeypatch):
    copied = list(calibrate.serve_readings(_context(), SEEDS[:1]))
    monkeypatch.setattr(calibrate, "stale_cache",
                        calibrate_in_place.stale_cache_in_place)
    assert list(calibrate.serve_readings(_context(), SEEDS[:1])) == copied
