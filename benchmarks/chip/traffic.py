"""The one generator of every cell's inputs, driven by a traffic file.

A traffic file (``traffic/<cell>.json``) holds parameters only; ``kind``
names the runner that serves it and the rest are sizes.  Everything drawn
here is a function of the run's seed and the item's index, so two runs of
one seed see the same inputs, and every seed sees the same sizes.

- ``serve_offline``: jobs of ``batch`` prompts of ``prompt_len`` token ids,
  uniform over the vocabulary; job ``j``'s prompts depend on (seed, j) only.
- ``train``: batches of ``batch`` rows of ``seq`` tokens from a Markov chain
  (each id the previous plus a step in [0, 17), modulo the vocabulary), the
  labels the tokens shifted by one; batch ``i`` depends on (seed, i) only.
  This is a copy of the program's synthetic data stream (``make_batch`` in
  ``repro.data.pipeline``), so that the reference can rebuild what the
  program's pipeline fed it without taking anything from the program.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

import numpy as np

HERE = Path(__file__).resolve().parent
STREAM_PROMPTS, STREAM_SAMPLE = 1, 2


def load(cell: str) -> dict:
    return json.loads((HERE / "traffic" / f"{cell}.json").read_text())


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def job_prompts(t: dict, vocab: int, seed: int, job: int) -> list:
    """Job ``job``'s prompts: ``batch`` lists of ``prompt_len`` ids."""
    ids = rng(seed, STREAM_PROMPTS, job).integers(
        0, vocab, size=(t["batch"], t["prompt_len"]))
    return ids.tolist()


def sample(seed: int, population: int, k: int) -> np.ndarray:
    """``k`` distinct indices of ``population``, drawn from the seed."""
    return np.sort(rng(seed, STREAM_SAMPLE).choice(
        population, size=min(k, population), replace=False))


def train_batch(vocab: int, batch: int, seq: int, seed: int,
                step: int) -> Dict[str, np.ndarray]:
    """Batch ``step`` of the program's synthetic stream for ``seed``."""
    r = np.random.default_rng(np.random.SeedSequence([seed, step]))
    starts = r.integers(0, vocab, size=(batch, 1))
    steps = r.integers(0, 17, size=(batch, seq))
    tokens = ((starts + np.cumsum(steps, axis=1)) % vocab).astype(np.int32)
    labels = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    return {"tokens": tokens, "labels": labels}
