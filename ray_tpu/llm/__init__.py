"""ray_tpu.llm — LLM serving and batch inference on the TPU-native stack.

Reference surface: python/ray/llm/_internal/serve/ (LLMServer
core/server/llm_server.py:127, OpenAI-compatible ingress
core/ingress/builder.py:213 build_openai_app) and batch processors
(llm/_internal/batch/processor/). Where the reference wraps vLLM's CUDA
engine, the engine HERE is in-framework JAX: `LLMServer` runs the Llama
model with a KV-cache decode loop (_generate.py); `LLMEngine` wraps the
continuous-batching `PagedEngine` (_engine.py), which serves every family
of `MODEL_FAMILIES` — `LLMConfig.model` names a family and a preset.
Serving replicas are ordinary serve deployments, so routing/autoscaling/
gang placement come from ray_tpu.serve.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.llm._generate import generate, init_cache

BOS, EOS = 256, 257
# family -> (model module, its config class, its `f(cfg, key)` that makes the
# seeded weights a server without a checkpoint serves, its step set: all
# `PagedEngine` knows of the family, "module" or "module:attribute", the
# interface in `llm/_engine.py`'s docstring). Every name is resolved when a
# model of the family is built or served, not before
MODEL_FAMILIES = {
    "llama": ("ray_tpu.models.llama", "LlamaConfig", "init_params",
              "ray_tpu.llm._engine:LLAMA_STEPS"),
    "ling": ("ray_tpu.models.ling", "LingConfig", "seeded_params",
             "ray_tpu.llm._ling_steps"),
    "solar": ("ray_tpu.models.solar", "SolarConfig", "seeded_params",
              "ray_tpu.llm._solar_steps"),
    "brumby": ("ray_tpu.models.brumby", "BrumbyConfig", "init_params",
               "ray_tpu.llm._brumby_steps"),
    "mellum": ("ray_tpu.models.mellum", "MellumConfig", "init_params",
               "ray_tpu.llm._mellum_steps"),
    "joyai": ("ray_tpu.models.joyai", "JoyAIConfig", "seeded_params",
              "ray_tpu.llm._joyai_steps")}


def step_set(cfg):
    """The step set of the family whose config class `cfg` is an instance
    of. The class is matched by its module and name, so that looking one up
    imports that family's steps and no other family's anything."""
    import importlib

    classes = {(c.__module__, c.__name__) for c in type(cfg).__mro__}
    for module, config_cls, _, steps in MODEL_FAMILIES.values():
        if (module, config_cls) in classes:
            path, _, attribute = steps.partition(":")
            found = importlib.import_module(path)
            return getattr(found, attribute) if attribute else found
    raise TypeError(
        f"{type(cfg).__name__} is the config class of no family in "
        f"MODEL_FAMILIES ({sorted(MODEL_FAMILIES)})")


class ByteTokenizer:
    """Dependency-free byte-level tokenizer (ids 0-255 = bytes, 256=BOS,
    257=EOS). Stands in for sentencepiece the way the reference's tests use
    mock engines (reference: llm/tests mock_vllm_engine.py)."""

    vocab_size = 258

    def encode(self, text: str) -> List[int]:
        return [BOS] + list(text.encode("utf-8"))

    def decode(self, ids: List[int]) -> str:
        return bytes(i for i in ids if i < 256).decode("utf-8", "replace")


@dataclass
class LLMConfig:
    """Reference: llm LLMConfig (model_loading_config + engine_kwargs)."""

    model_id: str = "llama-tiny-random"
    # "<family>:<preset>": a model family of `MODEL_FAMILIES` and a preset
    # (a classmethod of its config class); a bare preset is the Llama
    # family's. "tiny", "llama3_8b", "ling:ling3_flash", "ling:tiny",
    # "solar:solar_open2", "brumby:brumby_14b", "mellum:mellum2_12b",
    # "joyai:joyai_llm_flash"
    model: str = "tiny"
    model_overrides: Dict[str, Any] = field(default_factory=dict)
    checkpoint_path: Optional[str] = None  # pickled params pytree
    max_new_tokens: int = 32
    temperature: float = 0.0
    num_replicas: int = 1
    seed: int = 0

    def build_model(self):
        """(the family's config, its parameters): what `PagedEngine` takes.
        `LLMServer`'s one-request-at-a-time `generate` path runs the Llama
        family only."""
        import importlib

        import jax

        from ray_tpu.tpu.accelerator import check_granted_devices

        # a worker holding a chip grant must see exactly those chips on the
        # TPU backend before anything is built on it
        check_granted_devices()
        family, _, preset = self.model.rpartition(":")
        module, config_cls, seeded, _ = MODEL_FAMILIES[family or "llama"]
        model = importlib.import_module(module)
        init_params = getattr(model, seeded)
        cfg = getattr(getattr(model, config_cls), preset)(
            **self.model_overrides)
        assert cfg.vocab_size >= ByteTokenizer.vocab_size, (
            "model vocab must cover the byte tokenizer's 258 ids")
        if self.checkpoint_path:
            import pickle

            with open(self.checkpoint_path, "rb") as f:
                params = jax.device_put(pickle.load(f))
        else:
            # under jit: one compile instead of one per eager op, and the
            # float32 draws fuse into the cast to param_dtype instead of
            # materialising at full size beside the weights
            import functools

            params = jax.jit(functools.partial(init_params, cfg))(
                jax.random.PRNGKey(self.seed))
        return cfg, params


class LLMServer:
    """One serving replica (reference: llm_server.py:127). Deployed through
    ray_tpu.serve; __call__ speaks an OpenAI-completions-shaped dict."""

    def __init__(self, config: LLMConfig):
        self.config = config
        self.tokenizer = ByteTokenizer()
        self.cfg, self.params = config.build_model()
        import collections

        # rolling latency/throughput signals for the serve autoscaler
        # (the replica's stats() probe forwards autoscaling_stats())
        self._tps = collections.deque(maxlen=32)
        self._ttfts = collections.deque(maxlen=64)

    def autoscaling_stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        if self._ttfts:
            s = sorted(self._ttfts)
            out["ttft_p50_s"] = s[len(s) // 2]
        if self._tps:
            out["tokens_per_s"] = sum(self._tps) / len(self._tps)
        return out

    def __call__(self, payload: Dict[str, Any]) -> Any:
        if isinstance(payload, dict) and payload.get("stream"):
            # OpenAI-style streaming: return a generator of completion
            # chunks; serve's streaming plane + the proxy's SSE writer carry
            # them to the client incrementally (reference: the vLLM engine's
            # streaming completions through proxy.py:1031)
            return self._stream_chunks(payload)
        prompts = payload.get("prompt", "")
        single = isinstance(prompts, str)
        if single:
            prompts = [prompts]
        max_new = int(payload.get("max_tokens", self.config.max_new_tokens))
        temperature = float(
            payload.get("temperature", self.config.temperature))
        t0 = time.monotonic()
        token_prompts = [self.tokenizer.encode(p) for p in prompts]
        outs = generate(
            self.cfg, self.params, token_prompts,
            max_new_tokens=max_new, temperature=temperature,
            seed=self.config.seed, eos_id=EOS,
        )
        elapsed = time.monotonic() - t0
        total = sum(len(t) for t in outs)
        if total:
            self._tps.append(total / max(elapsed, 1e-9))
        choices = [
            {"index": i, "text": self.tokenizer.decode(toks),
             "finish_reason": "stop" if len(toks) < max_new else "length"}
            for i, toks in enumerate(outs)
        ]
        total_tokens = sum(len(t) for t in outs)
        return {
            "id": f"cmpl-{int(t0 * 1000)}",
            "object": "text_completion",
            "model": self.config.model_id,
            "choices": choices,
            "usage": {
                "completion_tokens": total_tokens,
                "tokens_per_s": round(total_tokens / max(elapsed, 1e-9), 2),
            },
        }

    def _stream_chunks(self, payload: Dict[str, Any]):
        from ray_tpu.llm._generate import generate_stream

        prompt = payload.get("prompt", "")
        if not isinstance(prompt, str):
            prompt = prompt[0] if prompt else ""
        max_new = int(payload.get("max_tokens", self.config.max_new_tokens))
        temperature = float(
            payload.get("temperature", self.config.temperature))
        cid = f"cmpl-{int(time.monotonic() * 1000)}"
        t0 = time.monotonic()
        n = 0
        # byte-level tokens: decode incrementally so multi-byte UTF-8
        # characters flush only at valid boundaries (a per-token decode
        # would stream U+FFFD fragments and corrupt reassembled text)
        import codecs

        dec = codecs.getincrementaldecoder("utf-8")(errors="replace")
        for tok in generate_stream(
                self.cfg, self.params, self.tokenizer.encode(prompt),
                max_new_tokens=max_new, temperature=temperature,
                seed=self.config.seed, eos_id=EOS):
            n += 1
            if n == 1:
                self._ttfts.append(time.monotonic() - t0)
            text = dec.decode(bytes([tok])) if tok < 256 else ""
            if not text:
                continue  # mid-character: fold into the next chunk
            yield {
                "id": cid,
                "object": "text_completion.chunk",
                "model": self.config.model_id,
                "choices": [{"index": 0, "text": text}],
            }
        tail = dec.decode(b"", final=True)
        if tail:
            yield {
                "id": cid,
                "object": "text_completion.chunk",
                "model": self.config.model_id,
                "choices": [{"index": 0, "text": tail}],
            }
        yield {
            "id": cid,
            "object": "text_completion.chunk",
            "model": self.config.model_id,
            "choices": [{"index": 0, "text": "",
                         "finish_reason": "stop" if n < max_new
                         else "length"}],
        }


import ray_tpu as _rt


@_rt.remote
class LLMEngine:
    """Async actor wrapping the continuous-batching paged-KV engine
    (reference: the vLLM engine actor inside LLMServer —
    vllm_engine.py:283). Many callers stream completions concurrently;
    requests landing mid-decode join the running batch at the next step
    boundary."""

    def __init__(self, config: LLMConfig, engine_config=None):
        from ray_tpu.llm._engine import EngineConfig, PagedEngine

        t0 = time.monotonic()
        self.config = config
        self.tokenizer = ByteTokenizer()
        cfg, params = config.build_model()
        self.engine = PagedEngine(
            cfg, params, engine_config or EngineConfig(), eos_id=EOS)
        # every program the loop can dispatch, before the first request
        self.engine.warm_up()
        self._t0 = None
        self._init_s = time.monotonic() - t0

    @_rt.method(num_returns="streaming")
    async def completions_stream(self, prompt: str,
                                 max_tokens: Optional[int] = None,
                                 temperature: Optional[float] = None,
                                 seed: Optional[int] = None):
        """Stream token ids for one completion (text via the byte
        tokenizer is a pure client-side decode). Per-call overrides fall
        back to the LLMConfig, like the non-streaming LLMServer path."""
        if self._t0 is None:
            self._t0 = time.monotonic()
        ids = self.tokenizer.encode(prompt)
        gen = self.engine.generate_stream(
            ids,
            max_tokens=(self.config.max_new_tokens
                        if max_tokens is None else max_tokens),
            temperature=(self.config.temperature
                         if temperature is None else temperature),
            seed=self.config.seed if seed is None else seed,
        )
        async for tok in gen:
            yield int(tok)

    @_rt.method(num_returns="streaming")
    async def completions_stream_prefilled(self, prompt_ids, kv,
                                           max_tokens: Optional[int] = None,
                                           temperature: Optional[float] = None,
                                           seed: Optional[int] = None):
        """Decode side of prefill/decode disaggregation: admit with KV
        block contents transferred from a remote PrefillWorker (reference:
        serving_patterns/prefill_decode + vLLM KV transfer connectors).

        `kv` may be the PrefillWorker's result dict (the ingress passes
        the prefill task's REF, so the blocks move owner -> this engine
        over the object plane directly — zero-copy shm when co-located —
        without materializing in the ingress process) or a bare
        (k, v, last_logits) tuple."""
        if isinstance(kv, dict):
            kv = (kv["k"], kv["v"], kv["last_logits"])
        if self._t0 is None:
            self._t0 = time.monotonic()
        gen = self.engine.generate_stream(
            list(prompt_ids),
            max_tokens=(self.config.max_new_tokens
                        if max_tokens is None else max_tokens),
            temperature=(self.config.temperature
                         if temperature is None else temperature),
            seed=self.config.seed if seed is None else seed,
            prefilled=tuple(kv),
        )
        async for tok in gen:
            yield int(tok)

    async def device_info(self) -> Dict[str, Any]:
        """What JAX shows this engine's process, next to what the node
        daemon granted it. A process numbers its own devices from 0, so
        engines on different chips are told apart by `granted_chips`."""
        import jax

        from ray_tpu.tpu.accelerator import granted_chips

        devices = jax.local_devices()
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "granted_chips": ",".join(granted_chips()),
            "init_s": round(self._init_s, 2),
        }

    async def check_prefill(self, prompt: str) -> Dict[str, Any]:
        """Prefill's last-position logits against the reference forward on
        the same prompt, computed here where the weights are (off the event
        loop: it compiles)."""
        import asyncio

        return await asyncio.to_thread(
            self.engine.check_prefill, self.tokenizer.encode(prompt))

    async def stats(self) -> Dict[str, Any]:
        s = self.engine.stats()
        elapsed = max(time.monotonic() - (self._t0 or time.monotonic()),
                      1e-9)
        s["tokens_per_s"] = round(s["tokens_out"] / elapsed, 2)
        return s

    async def autoscaling_stats(self) -> Dict[str, Any]:
        s = await self.stats()
        return {k: s[k] for k in ("ttft_p50_s", "tokens_per_s") if k in s}


def build_openai_app(config: LLMConfig, *, deployment_name: str = "v1"):
    """Deploy the completions endpoint; returns the serve handle
    (reference: build_openai_app core/ingress/builder.py:213 — the HTTP
    route is POST /<deployment_name>, our proxy's path convention)."""
    from ray_tpu import serve
    from ray_tpu.tpu.accelerator import chip_options

    deployment = serve.Deployment(
        LLMServer, deployment_name,
        num_replicas=config.num_replicas,
        # each replica builds the model, so each asks for its chip
        ray_actor_options=chip_options(),
        init_args=(config,),
    )
    return serve.run(deployment)


def batch_completions(config: LLMConfig, ds, *, prompt_column: str = "prompt",
                      output_column: str = "completion",
                      batch_size: int = 8):
    """Batch inference over a ray_tpu.data Dataset (reference: llm batch
    processor vllm_engine_stage.py). One model instance per map task."""

    def infer_batch(block):
        server = _server_singleton(config)
        prompts = [str(p) for p in block[prompt_column].tolist()]
        result = server({"prompt": prompts})
        import numpy as np

        out = dict(block)
        out[output_column] = np.array(
            [c["text"] for c in result["choices"]], dtype=object)
        return out

    return ds.map_batches(infer_batch)


_SINGLETON: Dict[tuple, LLMServer] = {}


def _server_singleton(config: LLMConfig) -> LLMServer:
    # keyed on everything that changes the loaded model — model_id alone
    # would silently serve the wrong weights when two configs share it
    key = (config.model_id, config.model, config.checkpoint_path,
           config.seed, tuple(sorted(config.model_overrides.items())))
    if key not in _SINGLETON:
        _SINGLETON[key] = LLMServer(config)
    return _SINGLETON[key]


__all__ = [
    "BOS",
    "EOS",
    "ByteTokenizer",
    "LLMConfig",
    "LLMServer",
    "batch_completions",
    "build_openai_app",
]

from ray_tpu._private.usage import record_library_usage as _rlu

_rlu("llm")
del _rlu
