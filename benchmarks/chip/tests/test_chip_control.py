"""The controls of the cells' comparisons, at a size a test run holds.

On the chip, at the cells' own sizes, ``calibrate.py`` reads the program,
its control and its faults over many seeds; the limits in the traffic files
were set from those readings (PERF.md).  Here the same code runs on the
CPU at a small size: the control (the reference in the next precision
below the configuration's) and the planted faults must read far above the
program, and the faults fail the cells' limits."""

import jax

from benchmarks.chip import calibrate, harness
from test_chip_rehearsal import full_width_logits

SEEDS = [3, 2**32 + 5, 11]


def _context(cell, model, traffic):
    c = harness.load_cell(cell)
    c.config = dict(c.config, model=dict(c.config["model"], **model))
    c.traffic = dict(c.traffic, **traffic)
    return harness.Context(c, 0, 0.0, False, jax.devices()[:1], {}, 0.0)


def _serve_context():
    # Four layers at a quarter of Yi's width (the head scaled to Yi's logit
    # spread by ``full_width_logits``); the float8 control's rounding goes
    # through fewer and narrower layers, so its gap is smaller than at full
    # size.
    return _context("yi9b-serve-offline",
                    {"n_layers": 4, "d_model": 1024, "n_heads": 8,
                     "n_kv_heads": 2, "d_head": 128, "d_ff": 2048,
                     "vocab": 4000},
                    {"batch": 2, "prompt_len": 32, "new_tokens": 16,
                     "check_requests": 2})


def test_serve_control_reads_far_above_the_program(monkeypatch):
    full_width_logits(monkeypatch)
    ctx = _serve_context()
    limit = ctx.traffic["limits"]["gap"]
    for r in calibrate.serve_readings(ctx, SEEDS):
        assert r["program"]["gap"] <= limit / 5, r
        assert r["control"]["gap"] >= 10 * max(r["program"]["gap"], 0.05), r
        assert r["stale_cache"]["gap"] > limit, r


def test_stale_cache_fault_holds_where_decode_donates_its_cache(monkeypatch):
    """A decode jitted with ``donate_argnums=(1,)`` deletes the cache it is
    given: the fault must hand back a copy taken before the step."""
    from repro.models import decode_step
    from repro.serving import ServeEngine

    full_width_logits(monkeypatch)
    seeds = SEEDS[1:2]
    kept = list(calibrate.serve_readings(_serve_context(), seeds))
    init = ServeEngine.__init__

    def donating(self, *a, **kw):
        init(self, *a, **kw)
        self.decode = jax.jit(
            lambda p, c, t: decode_step(p, self.cfg, self.run, c, t),
            donate_argnums=(1,))
    monkeypatch.setattr(ServeEngine, "__init__", donating)
    ctx = _serve_context()
    donated = list(calibrate.serve_readings(ctx, seeds))
    limit = ctx.traffic["limits"]["gap"]
    for k, d in zip(kept, donated):
        assert d["program"] == k["program"], (k, d)
        assert d["stale_cache"]["gap"] > limit, d


def test_train_control_and_fault_fail_the_limits():
    ctx = _context("mamba2-train-2k",
                   {"n_layers": 2, "d_model": 64, "ssm_state": 16,
                    "ssm_head_dim": 16, "ssm_chunk": 32, "vocab": 250},
                   {"batch": 2, "seq": 64})
    limits = ctx.traffic["limits"]

    def fails(numbers):
        return any(numbers[k] > limits[k] for k in limits)

    for r in calibrate.train_readings(ctx, SEEDS):
        assert not fails(r["program"]), r
        assert fails(r["control"]), r
        assert fails(r["half_batch"]), r
        assert r["control"]["delta"] > 3 * limits["delta"], r
