"""Batched serving engine: prefill + decode loop over waves of requests.

Requests are grouped into waves of up to ``batch_size`` prompts of one
length: prefill applies no pad mask and decode keeps one position for every
row, so a padded row would attend to its pads and its tokens would depend on
its wave-mates.  Greedy sampling; prefill is the jitted
``repro.models.prefill`` (the program ``jit_prefill``) and the decode step
the jitted ``repro.models.decode_step`` — the same function the dry-run
lowers for the ``decode_*`` cells.

Each wave is a host span ``serve.wave`` (``wave=<i>, rows=<b>``) of the
profiler's trace (``jax.profiler.TraceAnnotation``: one clock with the
device's operations, and next to no cost while no trace is taken), inside
it ``serve.prefill`` (dispatch of the prefill), ``serve.grow_cache``,
``serve.sample`` (argmax, its copy to the host and the per-row loop) and
``serve.decode`` (dispatch of a decode step, ``step=<k>``), each carrying
the wave's id.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig, RunConfig
from repro.models import decode_step, prefill


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    prompt: List[int]
    tokens: List[int]
    # With ``generate(..., return_logits=True)``: float32 (len(tokens), V);
    # row k holds the logits token k was chosen from (row 0 from prefill,
    # row k from decode step k).
    logits: Optional[np.ndarray] = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, run: Optional[RunConfig] = None,
                 batch_size: int = 4, max_len: int = 512):
        self.cfg = cfg
        self.run = run or RunConfig(attention_impl="chunked", attention_chunk=64,
                                    remat="none", zero=False)
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        # (params, cfg, run, tokens (B, S)) -> (logits (B, 1, V), cache)
        self.prefill = jax.jit(prefill, static_argnums=(1, 2))
        # (params, cache, tokens (B, 1)) -> (logits (B, 1, V), cache); the
        # cache is donated, so the step writes it in place and the cache
        # it was given is gone
        self.decode = jax.jit(
            lambda p, c, t: decode_step(p, cfg, self.run, c, t),
            donate_argnums=(1,))
        self._analysis = None
        self._waves = 0  # waves served so far: the next wave's id

    @property
    def analysis(self):
        """Co-resident kernel-analysis service (lazily constructed), sharing
        this process's analysis LRU — see ``repro.serving.analysis``."""
        if self._analysis is None:
            from repro.serving.analysis import AnalysisService
            self._analysis = AnalysisService()
        return self._analysis

    def analyze_asm(self, requests):
        """Serve a batch of assembly-analysis requests alongside decoding."""
        return self.analysis.analyze_batch(list(requests))

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 16,
                 eos_id: Optional[int] = None, frontend=None,
                 return_logits: bool = False) -> List[GenerationResult]:
        """Generate for a list of prompts, in waves of one prompt length.
        ``frontend`` rows go to a wave's slots in order."""
        by_len: Dict[int, list] = {}
        for rid, p in enumerate(prompts):
            by_len.setdefault(len(p), []).append((rid, p))
        results = []
        for group in by_len.values():
            for i in range(0, len(group), self.batch_size):
                results.extend(self._run_wave(
                    group[i:i + self.batch_size], max_new_tokens, eos_id,
                    frontend, return_logits))
        return sorted(results, key=lambda r: r.request_id)

    def prefill_wave(self, prompts: List[List[int]], max_new_tokens: int,
                     frontend=None, wave: int = 0):
        """Prefill prompts of one length; returns the last position's logits
        (B, 1, V) and a cache with room for ``max_new_tokens`` more tokens.
        ``wave``: the id its trace spans carry."""
        lengths = sorted({len(p) for p in prompts})
        if len(lengths) != 1:
            raise ValueError(
                f"a wave holds prompts of one length, got {lengths}")
        if frontend is not None:
            frontend = frontend[:len(prompts)]
        with TraceAnnotation("serve.prefill", wave=wave):
            logits, cache = self.prefill(
                self.params, self.cfg, self.run,
                jnp.asarray(np.asarray(prompts, np.int32)), frontend=frontend)
        with TraceAnnotation("serve.grow_cache", wave=wave):
            cache = self._grow_cache(cache, lengths[0] + max_new_tokens)
        return logits, cache

    def _run_wave(self, wave, max_new_tokens, eos_id, frontend, return_logits):
        b, wid = len(wave), self._waves
        self._waves += 1
        with TraceAnnotation("serve.wave", wave=wid, rows=b):
            logits, cache = self.prefill_wave(
                [p for _, p in wave], max_new_tokens, frontend, wave=wid)
            out_tokens = [[] for _ in range(b)]
            out_logits = [[] for _ in range(b)]
            done = [False] * b
            for step in range(max_new_tokens):
                with TraceAnnotation("serve.sample", wave=wid):
                    cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    picked = np.asarray(cur)
                    rows = (np.asarray(logits[:, -1], np.float32)
                            if return_logits else None)
                    for i in range(b):
                        if not done[i]:
                            out_tokens[i].append(int(picked[i]))
                            if return_logits:
                                out_logits[i].append(rows[i])
                            if eos_id is not None and picked[i] == eos_id:
                                done[i] = True
                if all(done) or step == max_new_tokens - 1:
                    break
                with TraceAnnotation("serve.decode", wave=wid, step=step):
                    logits, cache = self.decode(self.params, cache,
                                                cur[:, None])

        return [GenerationResult(
                    request_id=rid, prompt=list(p), tokens=out_tokens[i],
                    logits=(np.asarray(out_logits[i], np.float32)
                            if return_logits else None))
                for i, (rid, p) in enumerate(wave)]

    @staticmethod
    def _grow_cache(cache: Dict, new_len: int) -> Dict:
        """The prefill's cache with room for ``new_len`` positions: each KV
        stack (L, B, T, K, D) shorter than that padded with zeros along T.
        Every leaf is taken out of ``cache`` as it goes, so that only one
        stack is held twice at a time."""
        grown = {}
        for key in list(cache):
            leaf = cache.pop(key)
            if key in ("k", "v", "dk", "dv") and leaf.shape[2] < new_len:
                leaf = jnp.pad(leaf, ((0, 0),) * 2
                               + ((0, new_len - leaf.shape[2]),)
                               + ((0, 0),) * 2)
            grown[key] = leaf
        return grown
