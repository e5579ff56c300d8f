"""Batched serving: prefill + greedy decode over the model zoo.

`decode_step` handles S >= 1 token writes, so prefill is just a wide decode
onto an empty cache; generation then proceeds one token per step. The
request batcher pads a set of prompts to a common length and serves them as
one batch (continuous batching at real scale slots new requests into
finished cache rows; the slot logic is the same dynamic-update the cache
already uses).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.dist.sharding import place_on_mesh, use_mesh
from repro.models import init_params, registry
from repro.models.base import ArchConfig


@dataclasses.dataclass
class ServeSession:
    cfg: ArchConfig
    params: dict
    max_len: int
    mesh: Optional[jax.sharding.Mesh] = None  # None => single-device

    def __post_init__(self):
        self.fns = registry.model_fns(self.cfg)
        self.params = place_on_mesh(
            self.params, self.fns.param_structure(self.cfg), self.mesh)
        self._decode = jax.jit(
            lambda p, c, t: self.fns.decode_step(self.cfg, p, c, t))

    def _empty_cache(self, batch: int):
        structure = self.fns.cache_structure(self.cfg, batch, self.max_len)
        cache = init_params(structure, jax.random.key(0))
        return place_on_mesh(cache, structure, self.mesh)

    def _step(self, cache, tokens):
        """One decode call; an MoE model's pairs dropped past capacity are
        recorded as counter ``moe.dropped_pairs``."""
        logits, cache = self._decode(self.params, cache, tokens)
        if "moe_dropped" in cache:
            spans.count("moe.dropped_pairs", int(cache["moe_dropped"]))
        return logits, cache

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 8) -> list[list[int]]:
        B = len(prompts)
        plen = max(len(p) for p in prompts)
        toks = np.zeros((B, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p  # left-pad
        cache = self._empty_cache(B)
        out = [list(p) for p in prompts]
        with use_mesh(self.mesh):
            logits, cache = self._step(cache, jnp.asarray(toks))  # prefill
            cur = jnp.argmax(logits[:, -1:, : self.cfg.vocab_size], axis=-1
                             ).astype(jnp.int32)
            for _ in range(max_new_tokens):
                # one device->host transfer for the whole batch per step
                # (a per-request int(cur[i, 0]) would sync B times/step)
                step_toks = np.asarray(cur)[:, 0]
                for o, t in zip(out, step_toks.tolist()):
                    o.append(t)
                logits, cache = self._step(cache, cur)
                cur = jnp.argmax(logits[:, -1:, : self.cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)
        return out

    def layout_plan(self, *, tokens: Optional[int] = None,
                    weight_bits: int = 4, service=None):
        """The layout plan serving this session's architecture trace.

        Compiles (or fetches from the content-addressed plan cache) the
        ``arch/<id>`` workload at this session's context length via
        ``repro.serve.PlanService`` -- the same plan the serve-bench
        traffic path dispatches.
        """
        from repro.serve.service import PlanService, Request

        if service is None:
            service = PlanService()
        req = Request(id=0, arch=self.cfg.name,
                      tokens=tokens or self.max_len,
                      weight_bits=weight_bits)
        return service.compile(req).plan
