"""LLM serving patterns: prefill/decode disaggregation, KV-aware routing,
data-parallel engine gangs.

Reference surface:
- python/ray/llm/_internal/serve/serving_patterns/prefill_decode/
  builder.py:236-238 — separate prefill and decode deployments with KV
  transfer between them;
- python/ray/llm/_internal/serve/routing_policies/kv_aware/ — route
  requests sharing a prompt prefix to the replica most likely to hold its
  KV state;
- python/ray/llm/_internal/serve/serving_patterns/data_parallel/
  dp_server.py:247-276 — a ranked gang of engine replicas behind one
  ingress.

TPU-first redesign: prefill workers compute the prompt's KV into a
minimal block pool and ship the block CONTENTS (host-staged numpy today;
the device plane carries them as arrays) to a decode engine, which
scatters them into its paged pool and admits the request mid-decode —
prefill compute and decode batching scale independently. The PD ingress
additionally memoizes whole-prompt prefills (LRU), so repeated prompts
skip prefill entirely — the measurable form of KV reuse the router's
prefix affinity is aiming at.
"""

from __future__ import annotations

import collections
import hashlib
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.llm import EOS, ByteTokenizer, LLMConfig, step_set
from ray_tpu.llm._engine import EngineConfig, prefill_fresh_pool
from ray_tpu.tpu.accelerator import chip_options


@ray_tpu.remote
class PrefillWorker:
    """Computes a prompt's KV cache into a minimal block pool and returns
    the block contents + last-position logits (the prefill side of P/D
    disaggregation)."""

    def __init__(self, config: LLMConfig, engine_config: Optional[dict] = None):
        self.config = config
        self.ecfg = EngineConfig(**(engine_config or {}))
        self.cfg, self.params = config.build_model()
        steps = step_set(self.cfg)
        if not hasattr(steps, "make_prefill"):
            raise ValueError(
                f"no PrefillWorker for {type(self.cfg).__name__}: its "
                "prompts run as chunks in the decode step and its step set "
                "brings no whole-prompt prefill program (nor could its "
                "decode engines take transferred blocks: make_kv_inject)")
        self._prefill = steps.make_prefill(self.cfg, self.ecfg)
        self._served = 0

    def prefill(self, prompt_ids: List[int]) -> Dict[str, Any]:
        logits, kc, vc, nb = prefill_fresh_pool(
            self.cfg, self.ecfg, self._prefill, self.params, prompt_ids)
        self._served += 1
        return {
            "k": np.asarray(kc[:, 1:nb + 1]),
            "v": np.asarray(vc[:, 1:nb + 1]),
            "last_logits": np.asarray(logits),
        }

    def stats(self) -> Dict[str, Any]:
        return {"prefills": self._served}


def _prefix_key(prompt_ids: List[int], block: int) -> str:
    """Block-aligned prefix fingerprint for KV-aware routing."""
    head = prompt_ids[: max(block, 1)]
    return hashlib.blake2b(np.asarray(head, np.int32).tobytes(),
                           digest_size=8).hexdigest()


class KvAwareRouter:
    """Prefix-affinity replica choice (reference: routing_policies/
    kv_aware/): requests sharing a block-aligned prompt prefix route to the
    same decode engine, maximizing pool-local KV/prefill-cache reuse;
    unseen prefixes go to the least-loaded engine."""

    def __init__(self, n: int, block: int):
        self.n = n
        self.block = block
        self._affinity: "collections.OrderedDict[str, int]" = (
            collections.OrderedDict())
        self.load = [0] * n

    def pick(self, prompt_ids: List[int]) -> Tuple[int, str]:
        key = _prefix_key(prompt_ids, self.block)
        i = self._affinity.get(key)
        if i is None:
            i = min(range(self.n), key=lambda j: self.load[j])
            self._affinity[key] = i
            while len(self._affinity) > 4096:
                self._affinity.popitem(last=False)
        else:
            self._affinity.move_to_end(key)
        self.load[i] += 1
        return i, key

    def done(self, i: int):
        self.load[i] = max(0, self.load[i] - 1)


class PrefillDecodeIngress:
    """Serve deployment: routes each completion through the prefill pool
    then a KV-aware-chosen decode engine, streaming tokens back
    (reference: prefill_decode/builder.py)."""

    def __init__(self, config: LLMConfig, *, num_prefill: int = 1,
                 num_decode: int = 1, engine_config: Optional[dict] = None,
                 prefill_cache_size: int = 32):
        from ray_tpu.llm import LLMEngine

        self.config = config
        self.tokenizer = ByteTokenizer()
        ecfg = dict(engine_config or {})
        self.block = int(ecfg.get("kv_block_size", 16))
        # every actor that builds the model asks for its chip
        chip = chip_options()
        self.prefill_workers = [
            PrefillWorker.options(**chip).remote(config, ecfg)
            for _ in range(num_prefill)]
        self.decoders = [
            LLMEngine.options(**chip).remote(config, EngineConfig(**ecfg))
            for _ in range(num_decode)]
        self.router = KvAwareRouter(num_decode, self.block)
        self._pf_rr = 0
        # whole-prompt prefill memo: repeated prompts skip prefill entirely
        self._pf_cache: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict())
        self._pf_cache_size = prefill_cache_size
        self.prefill_cache_hits = 0

    async def __call__(self, payload: Dict[str, Any]):
        prompt = payload.get("prompt", "")
        if not isinstance(prompt, str):
            prompt = prompt[0] if prompt else ""
        ids = self.tokenizer.encode(prompt)
        max_new = int(payload.get("max_tokens", self.config.max_new_tokens))
        temperature = float(
            payload.get("temperature", self.config.temperature))
        full_key = hashlib.blake2b(
            np.asarray(ids, np.int32).tobytes(), digest_size=8).hexdigest()
        # the cache holds the prefill task's REF, never the blocks: the KV
        # moves prefill-worker -> decode-engine over the object plane
        # (zero-copy shm when co-located, chunked pull across nodes)
        # without ever materializing in this ingress process — the r4
        # review's "full KV through the host plane per request" hop is gone
        kv_ref = self._pf_cache.get(full_key)
        if kv_ref is not None:
            self._pf_cache.move_to_end(full_key)
            self.prefill_cache_hits += 1
        else:
            pf = self.prefill_workers[
                self._pf_rr % len(self.prefill_workers)]
            self._pf_rr += 1
            kv_ref = pf.prefill.remote(ids)
            self._pf_cache[full_key] = kv_ref
            while len(self._pf_cache) > self._pf_cache_size:
                self._pf_cache.popitem(last=False)
        i, _ = self.router.pick(ids)
        try:
            toks: List[int] = []
            gen = self.decoders[i].completions_stream_prefilled.options(
                num_returns="streaming").remote(
                ids, kv_ref,
                max_tokens=max_new, temperature=temperature,
                seed=self.config.seed)
            async for ref in gen:
                toks.append(await ref)
        except Exception:
            # a failed prefill ref must not poison the cache: retries of
            # the SAME prompt would keep hitting the dead ref until 32
            # other prompts evicted it
            self._pf_cache.pop(full_key, None)
            raise
        finally:
            self.router.done(i)
        return {
            "object": "text_completion",
            "model": self.config.model_id,
            "choices": [{"index": 0, "text": self.tokenizer.decode(toks),
                         "finish_reason": "stop" if len(toks) < max_new
                         else "length"}],
            "usage": {"completion_tokens": len(toks),
                      "prefill_cache_hits": self.prefill_cache_hits,
                      "decode_replica": i},
        }

    def stats(self) -> Dict[str, Any]:
        return {"prefill_cache_hits": self.prefill_cache_hits,
                "router_load": list(self.router.load)}


def build_pd_app(config: LLMConfig, *, num_prefill: int = 1,
                 num_decode: int = 1, deployment_name: str = "pd",
                 engine_config: Optional[dict] = None):
    """Deploy the prefill/decode-disaggregated completions endpoint;
    returns the serve handle (reference: prefill_decode/builder.py)."""
    from ray_tpu import serve

    deployment = serve.Deployment(
        PrefillDecodeIngress, deployment_name, num_replicas=1,
        init_args=(config,),
        init_kwargs={"num_prefill": num_prefill, "num_decode": num_decode,
                     "engine_config": engine_config},
    )
    return serve.run(deployment)


class DPEngineGroup:
    """A RANKED data-parallel gang of engine actors behind one ingress
    (reference: serving_patterns/data_parallel/dp_server.py:247-276 +
    GangContext): every engine knows its rank/world, requests spread by
    least-in-flight, and the group exposes aggregate stats."""

    def __init__(self, config: LLMConfig, dp_size: int,
                 engine_config: Optional[dict] = None):
        from ray_tpu.llm import LLMEngine

        self.config = config
        self.tokenizer = ByteTokenizer()
        ecfg = EngineConfig(**(engine_config or {}))
        # one engine per chip: each asks for its own
        chip = chip_options()
        self.engines = [
            LLMEngine.options(runtime_env={"env_vars": {
                "RT_DP_RANK": str(r), "RT_DP_SIZE": str(dp_size)}},
                **chip,
            ).remote(config, ecfg)
            for r in range(dp_size)
        ]
        self.load = [0] * dp_size

    async def stats(self) -> List[Dict[str, Any]]:
        """Per-rank engine stats, each with the device its process holds."""
        import asyncio

        async def one(e):
            s, device = await asyncio.gather(
                e.stats.remote(), e.device_info.remote())
            return {**s, "device": device}

        return list(await asyncio.gather(*map(one, self.engines)))

    async def check_prefill(self, prompt: str) -> Dict[str, Any]:
        """Rank 0's prefill-against-forward logits check (see LLMEngine)."""
        return await self.engines[0].check_prefill.remote(prompt)

    async def __call__(self, payload: Dict[str, Any]):
        prompt = payload.get("prompt", "")
        if not isinstance(prompt, str):
            prompt = prompt[0] if prompt else ""
        max_new = int(payload.get("max_tokens", self.config.max_new_tokens))
        i = min(range(len(self.engines)), key=lambda j: self.load[j])
        self.load[i] += 1
        # this replica's monotonic clock: the call's start, the first
        # token's arrival and the last, for the answer's usage
        t_call = time.monotonic()
        t_first = 0.0
        try:
            toks: List[int] = []
            gen = self.engines[i].completions_stream.options(
                num_returns="streaming").remote(
                prompt, max_tokens=max_new,
                temperature=float(payload.get(
                    "temperature", self.config.temperature)))
            async for ref in gen:
                toks.append(await ref)
                if not t_first:
                    t_first = time.monotonic()
        finally:
            self.load[i] = max(0, self.load[i] - 1)
        t_last = time.monotonic()
        text = self.tokenizer.decode(toks)
        return {
            "object": "text_completion",
            "model": self.config.model_id,
            # token_ids: the byte tokenizer's text drops every id >= 256,
            # so only the ids say what the model produced
            "choices": [{"index": 0, "text": text, "token_ids": toks,
                         "finish_reason": "stop" if len(toks) < max_new
                         else "length"}],
            # ttft_s: call start -> first token here (queue + prefill + one
            # hop); total_s: call start -> last token. A stream that gave
            # no token has no first one: ttft_s is then total_s
            "usage": {"completion_tokens": len(toks), "dp_rank": i,
                      "ttft_s": (t_first or t_last) - t_call,
                      "total_s": t_last - t_call},
        }


def build_dp_app(config: LLMConfig, *, dp_size: int = 2,
                 deployment_name: str = "dp",
                 engine_config: Optional[dict] = None):
    """Deploy a data-parallel engine gang behind one route (reference:
    data_parallel/dp_server.py)."""
    from ray_tpu import serve

    deployment = serve.Deployment(
        DPEngineGroup, deployment_name, num_replicas=1,
        init_args=(config, dp_size),
        init_kwargs={"engine_config": engine_config},
    )
    return serve.run(deployment)


__all__ = [
    "DPEngineGroup",
    "KvAwareRouter",
    "PrefillDecodeIngress",
    "PrefillWorker",
    "build_dp_app",
    "build_pd_app",
]
