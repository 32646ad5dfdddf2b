#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that ray_tpu starts on the chip.

Drives the framework's two model paths once through the entry points a user
calls, at Llama-3-8B widths with depth cut and seeded random weights:

  serve  ray_tpu.init() -> serve.start() -> build_dp_app(dp_size=chips) ->
         HTTP POSTs through the proxy -> DPEngineGroup -> LLMEngine ->
         PagedEngine, one engine process per chip;
  train  JaxTrainer(use_tpu=True).fit(): one worker holding every chip,
         make_train_step on an fsdp mesh with the flash kernels, then every
         Pallas kernel of ops/flash_attention.py against its XLA reference.

    python chip_smoke.py [--phase serve|train]

Exit 0 only if every phase passed on TPU chips. This process never
initialises a JAX backend (a parent that holds the chip starves its
workers): chip count comes from ray_tpu.cluster_resources(), platform and
device_kind from the processes that hold the chips. Each phase prints one
JSON line, then a summary line ("claim" is null: nothing here claims a gain),
and the last line of stdout is the result the chip check reads, with exactly
these keys: {"ok": true, "device": {"platform", "kind", "count"}}. A failed
run prints no result line. Timings are smoke timings of one run, not metrics.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import os
import signal
import sys
import time
import traceback
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
DEADLINE_S = 1140

# --------------------------------------------------------------------------
# presets. "full" is what runs on the chip; "tiny" exists for the CPU tests
# (tests/test_chip_smoke.py) and is reachable only through --preset.
# --------------------------------------------------------------------------

FULL = {
    "serve": {
        # published Llama-3-8B widths (dim 4096, 32 query / 8 KV heads of 128,
        # FFN 14336, vocabulary 128,256), nothing narrowed. Depth is the cut:
        # 218M parameters a layer + 1.05B in embedding and head, so 8 layers
        # are 2.8B = 5.6 GB in bf16 on a 16 GB chip.
        "model": "llama3_8b",
        "n_layers": 8,
        "param_dtype": "bfloat16",
        "max_seq_len": 2048,
        # sized for use, not the unit-test defaults: 8 slots, 2048-token
        # sequences, a 16k-token pool (8 layers x 4 KB x 16k = 0.5 GB)
        "engine": {"max_num_seqs": 8, "kv_block_size": 16,
                   "num_kv_blocks": 1024, "max_model_len": 2048},
        "short_bytes": 100, "long_bytes": 900,
        "max_tokens": 32, "lead_max_tokens": 128,
    },
    "train": {
        # same family and widths. float32 weights + grads + Adam are 16 B a
        # parameter, so the 128k-row vocabulary alone would be 16.8 GB: the
        # cut holds one chip's quarter share of the vocabulary rows (32,064)
        # and 2 layers = 699M parameters = 11.2 GB of state.
        "model": "llama3_8b",
        "n_layers": 2,
        "vocab_size": 32064,
        "seq": 2048,
        "batch_per_chip": 2,
        "steps": 6,
        "dtype": "bfloat16", "param_dtype": "float32",
    },
    # (whole-sequence s, streamed s): fwd/dq stream above s*hd = 8192*128,
    # dkv above 4096*128; 16384 puts all three in the streamed regime.
    "kernels": {"s_whole": 2048, "s_stream": 16384, "ref_block": 4096,
                "hop_sq": 4608, "hop_sk": 8704, "block": 512,
                "interpret": False},
}

TINY = {
    "serve": {
        "model": "tiny", "n_layers": 2, "param_dtype": "float32",
        "max_seq_len": 256,
        "engine": {"max_num_seqs": 4, "kv_block_size": 16,
                   "num_kv_blocks": 64, "max_model_len": 256},
        "short_bytes": 20, "long_bytes": 100,
        "max_tokens": 8, "lead_max_tokens": 96,
    },
    "train": {
        # head_dim 128 so the kernels' shape constraint holds
        "model": "tiny", "n_layers": 2, "vocab_size": 512, "dim": 256,
        "n_heads": 2, "n_kv_heads": 1, "seq": 256, "batch_per_chip": 1,
        "steps": 6, "dtype": "float32", "param_dtype": "float32",
    },
    "kernels": {"s_whole": 128, "s_stream": 256, "ref_block": 128,
                "hop_sq": 256, "hop_sk": 256, "block": 128,
                "interpret": True},
}

PRESETS = {"full": FULL, "tiny": TINY}


class SmokeFailure(AssertionError):
    """A phase ran and its output was wrong."""


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# serve phase (driver side: HTTP client + checks on what came back)
# --------------------------------------------------------------------------


def _prompt(n_bytes: int, salt: str) -> str:
    words = ("the quick brown fox jumps over the lazy dog while "
             "continuous batching admits requests mid decode ").split()
    out = [salt]
    i = 0
    while sum(len(w) + 1 for w in out) < n_bytes:
        out.append(words[i % len(words)])
        i += 1
    return " ".join(out)[:n_bytes]


def _post(url: str, payload: dict, timeout: float = 900.0) -> dict:
    """One completion through the proxy. HTTP 200 is not success until the
    body's choices/usage are there: engine errors travel as queue items and
    surface as an error body."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, body = resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"HTTP {e.code}: {e.read().decode('utf-8', 'replace')}") from e
    _require(status == 200, f"HTTP {status}: {body}")
    result = body.get("result") if isinstance(body, dict) else None
    _require(isinstance(result, dict) and result.get("choices")
             and "usage" in result, f"malformed completion body: {body}")
    want = payload["max_tokens"]
    got = result["usage"]["completion_tokens"]
    _require(got == want,
             f"incomplete response: {got} of {want} tokens: {result}")
    ids = result["choices"][0]["token_ids"]
    _require(len(ids) == want, f"token_ids length {len(ids)} != {want}")
    return {"seconds": time.monotonic() - t0, "token_ids": ids,
            "text": result["choices"][0]["text"],
            "dp_rank": result["usage"]["dp_rank"]}


def serve_phase(chips: int, preset: dict) -> dict:
    import jax.numpy as jnp  # dtype names only: no backend is touched

    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving_patterns import build_dp_app

    p = preset["serve"]
    t_start = time.monotonic()
    base = serve.start(http_port=0)
    config = LLMConfig(
        model_id="chip-smoke", model=p["model"],
        model_overrides={"n_layers": p["n_layers"],
                         "param_dtype": getattr(jnp, p["param_dtype"]),
                         "max_seq_len": p["max_seq_len"]},
        max_new_tokens=p["max_tokens"])
    handle = build_dp_app(config, dp_size=chips, deployment_name="smoke",
                          engine_config=p["engine"])
    url = f"{base}/smoke"
    n_tok = p["max_tokens"]

    def ask(prompt, max_tokens=n_tok):
        return _post(url, {"prompt": prompt, "max_tokens": max_tokens,
                           "temperature": 0.0})

    # 1. one long prompt three times in a row on rank 0: cold (full prefill,
    #    compiles), then twice over its cached prefix blocks
    repeated = _prompt(p["long_bytes"], "repeat")
    first = ask(repeated)
    first_response_s = time.monotonic() - t_start
    again = [ask(repeated), ask(repeated)]
    # greedy decoding of one prompt down one code path is deterministic
    _require(again[0]["token_ids"] == again[1]["token_ids"],
             "the repeated greedy prompt gave different tokens on the same "
             f"(prefix-cached) path: {again[0]['token_ids']} vs "
             f"{again[1]['token_ids']}")
    _require(again[0]["text"] == again[1]["text"],
             "the repeated greedy prompt gave different text")
    # full prefill vs suffix-over-cached-prefix sum attention in another
    # order, so in bf16 a near-tie among 128k random logits may flip one
    # argmax and everything after it; the agreeing prefix is reported
    agree = 0
    for a, b in zip(first["token_ids"], again[0]["token_ids"]):
        if a != b:
            break
        agree += 1

    # 2. a lead request that decodes for a while, then a concurrent burst in
    #    both prompt-length buckets while it runs: mid-decode admission on
    #    rank 0, and enough load in flight that every rank answers
    def stats():
        return handle.method("stats").remote().result(timeout=120)

    burst_prompts = [
        _prompt(p["short_bytes" if i % 2 else "long_bytes"], f"burst{i}")
        for i in range(max(6, 2 * chips + 2))]
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=len(burst_prompts) + 1) as pool:
        lead = pool.submit(ask, _prompt(p["short_bytes"], "lead"),
                           p["lead_max_tokens"])
        deadline = time.monotonic() + 600
        while not any(s["active_slots"] for s in stats()):
            _require(not lead.done() and time.monotonic() < deadline,
                     "the lead request never showed up as an active slot")
            time.sleep(0.01)
        burst = [pool.submit(ask, bp) for bp in burst_prompts]
        answers = [lead.result()] + [f.result() for f in burst]
    n_requests = 3 + len(answers)

    # 3. what the engines say
    per_rank = stats()
    _require(len(per_rank) == chips, f"{len(per_rank)} engines, {chips} chips")
    ranks = {a["dp_rank"] for a in answers} | {first["dp_rank"]}
    _require(ranks == set(range(chips)),
             f"dp ranks that answered: {sorted(ranks)} of {chips}")
    for r, s in enumerate(per_rank):
        _require(s["steps"] > 0, f"rank {r}: no decode step ran: {s}")
        # (the CPU preset's engines see every virtual CPU device)
        _require(s["device"]["platform"] != "tpu"
                 or s["device"]["device_count"] == 1,
                 f"rank {r} sees {s['device']['device_count']} devices")
    _require(sum(s["mid_decode_admissions"] for s in per_rank) > 0,
             f"no request was admitted mid-decode: {per_rank}")
    _require(per_rank[0]["prefix_cache"]["block_hits"] > 0,
             f"no prefix-cache block hit: {per_rank[0]['prefix_cache']}")
    grants = [s["device"]["granted_chips"] for s in per_rank]
    _require(len(set(grants)) == chips and all(grants),
             f"engines do not hold distinct chips: {grants}")
    platforms = {s["device"]["platform"] for s in per_rank}
    kinds = {s["device"]["device_kind"] for s in per_rank}
    _require(len(platforms) == 1 and len(kinds) == 1,
             f"mixed devices: {platforms} {kinds}")

    # 4. numerics, in the engine's process: prefill's last-position logits
    #    against models.llama.forward on the same prompt
    check = handle.method("check_prefill").remote(
        _prompt(p["short_bytes"], "check")).result(timeout=900)
    # Both compute in bfloat16 activations (LlamaConfig.dtype) and differ
    # only in padding (prefill pads to a power-of-two bucket) and reduction
    # order. bf16 carries 8 significant bits, so a logit of magnitude m is
    # known to m * 2^-8; 4 such steps cover roundings that fall differently
    # in a few of the layers (measured on v5e: 0.7 of a step) without
    # admitting a wrong mask or position: random-weight logits have unit
    # spread, and those errors move them by whole units.
    tol = 4 * 2.0 ** -8 * max(1.0, check["max_abs_ref"])
    _require(check["finite"], f"prefill logits not finite: {check}")
    _require(check["max_abs_diff"] <= tol,
             f"prefill logits differ from forward by "
             f"{check['max_abs_diff']} > {tol}: {check}")

    steady = [a["seconds"] for a in answers[1:]]
    return {
        "phase": "serve", "ok": True,
        "platform": platforms.pop(), "device_kind": kinds.pop(),
        "device_count": sum(s["device"]["device_count"] for s in per_rank),
        "engine_processes": chips, "granted_chips": grants,
        "model_cut": {k: p[k] for k in ("model", "n_layers", "param_dtype",
                                        "max_seq_len")},
        "engine": p["engine"],
        "requests": n_requests,
        "prefix_cache": per_rank[0]["prefix_cache"],
        "mid_decode_admissions": [s["mid_decode_admissions"]
                                  for s in per_rank],
        "cold_vs_cached_tokens_agree": f"{agree}/{n_tok}",
        "prefill_vs_forward": {**check, "tolerance": tol},
        "smoke_timings_s": {
            "engine_init": [s["device"]["init_s"] for s in per_rank],
            "start_to_first_response": round(first_response_s, 2),
            "cached_repeat": round(again[1]["seconds"], 3),
            "burst_request_median": round(sorted(steady)[len(steady) // 2], 3),
            "cached_repeat_per_token": round(again[1]["seconds"] / n_tok, 4),
        },
    }


# --------------------------------------------------------------------------
# train phase (these two functions run inside the train worker: it holds
# the chips, so the kernel checks run there too)
# --------------------------------------------------------------------------


def kernel_cases(k: dict):
    """(name, kernel fn, reference fn, make_args) for every Pallas kernel of
    ops/flash_attention.py through its public entry point: fwd, dq, dkv in
    the whole-sequence and the streamed regime, flash_chunk_bhsd and
    flash_hop_bwd. head_dim 128, GQA 4:1. The name's tail labels the
    outputs."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    dt = jnp.float32 if k["interpret"] else jnp.bfloat16
    h, kvh, hd, blk = 4, 1, 128, k["block"]

    def rand(i, *shape):
        return jax.random.normal(jax.random.key(i), shape, jnp.float32
                                 ).astype(dt)

    def ref_attention(q, kk, v, block):
        # the XLA reference in query blocks: a full score matrix at s=16384
        # is 1 GB per head in float32
        return jnp.concatenate([
            fa._xla_attention_bhsd(q[:, :, i:i + block], kk[:, :, :i + block],
                                   v[:, :, :i + block], True)
            for i in range(0, q.shape[2], block)], axis=2)

    def fwd_bwd(attn, q, kk, v, g):
        out, vjp = jax.vjp(attn, q, kk, v)
        return (out, *vjp(g))

    for regime, s in (("whole", k["s_whole"]), ("streamed", k["s_stream"])):
        yield (f"flash_attention[{regime} s={s}]/fwd,dq,dkv_dk,dkv_dv",
               functools.partial(fwd_bwd, functools.partial(
                   fa.flash_attention_bhsd, causal=True, block_q=blk,
                   block_k=blk)),
               functools.partial(fwd_bwd, functools.partial(
                   ref_attention, block=min(s, k["ref_block"]))),
               lambda s=s: (rand(1, 1, h, s, hd), rand(2, 1, kvh, s, hd),
                            rand(3, 1, kvh, s, hd), rand(4, 1, h, s, hd)))

    def empty_carry(sq):
        return (jnp.zeros((1, h, sq, hd), jnp.float32),
                jnp.full((1, h, sq, 1), fa.NEG_INF, jnp.float32),
                jnp.zeros((1, h, sq, 1), jnp.float32))

    def chunk_args(sq, sk):
        q = rand(5, 1, h, sq, hd)
        # a non-trivial carry: one earlier hop already accumulated
        carry = fa._chunk_xla(q, rand(8, 1, kvh, sq, hd),
                              rand(9, 1, kvh, sq, hd), *empty_carry(sq),
                              False)
        return (q, rand(6, 1, kvh, sk, hd), rand(7, 1, kvh, sk, hd), *carry)

    for regime, sq, sk, causal in (
            ("whole causal", k["s_whole"], k["s_whole"], True),
            ("whole", k["s_whole"], k["s_whole"], False),
            ("streamed", k["s_whole"], k["s_stream"], False)):
        yield (f"flash_chunk_bhsd[{regime} sq={sq} sk={sk}]/o,m,l",
               functools.partial(fa.flash_chunk_bhsd, causal=causal,
                                 block_q=blk, block_k=blk),
               functools.partial(fa._chunk_xla, causal=causal),
               functools.partial(chunk_args, sq, sk))

    def hop_args(sq, sk, causal):
        q, kk, v, g = (rand(10, 1, h, sq, hd), rand(11, 1, kvh, sk, hd),
                       rand(12, 1, kvh, sk, hd), rand(13, 1, h, sq, hd))
        # lse/delta rows as the ring forward would save them
        o, m, l = fa._chunk_xla(q, kk, v, *empty_carry(sq), causal)
        delta = jnp.sum(g.astype(jnp.float32) * (o / l), axis=-1,
                        keepdims=True)
        return q, kk, v, g, m + jnp.log(l), delta

    for regime, sq, sk, causal in (
            ("whole causal", k["s_whole"], k["s_whole"], True),
            ("streamed", k["hop_sq"], k["hop_sk"], False)):
        yield (f"flash_hop_bwd[{regime} sq={sq} sk={sk}]/dq,dk,dv",
               functools.partial(fa.flash_hop_bwd, causal=causal,
                                 block_q=blk, block_k=blk),
               functools.partial(fa._hop_bwd_xla, causal=causal),
               functools.partial(hop_args, sq, sk, causal))


def kernel_checks(k: dict) -> list:
    """Run every kernel case compiled (interpreted only under the CPU
    preset) and compare with its XLA reference. On a TPU backend the entry
    points run the kernels or raise; the lowered text is checked for the
    custom call all the same."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    on_tpu = jax.default_backend() == "tpu"
    _require(on_tpu or k["interpret"], "kernel checks need a TPU backend")
    # Reference and kernel take the same bf16 inputs and accumulate in
    # float32; they differ in the precision of the probabilities fed to the
    # second matmul (the reference rounds them to the input dtype) and in
    # summation order: a few bf16 steps (2^-8 relative each) of the largest
    # output. Allowed: 2^-5 = 3% of max|reference| — a wrong mask, block
    # index or scale moves outputs by tens of percent. The CPU preset runs
    # float32 in the interpreter and gets 1e-4.
    rel_tol = 1e-4 if k["interpret"] else 2.0 ** -5
    saved = fa._INTERPRET, fa._STREAM_KV_ELEMS, fa._STREAM_QDO_ELEMS
    if k["interpret"]:
        # thresholds scaled down with the shapes, so the streamed regime is
        # still the one that runs
        fa._INTERPRET = True
        fa._STREAM_KV_ELEMS = fa._STREAM_QDO_ELEMS = k["s_whole"] * 128
    results = []
    try:
        for name, fn, ref_fn, make_args in kernel_cases(k):
            args = jax.jit(make_args)()
            lowered = jax.jit(fn).lower(*args)
            compiled_pallas = "tpu_custom_call" in lowered.as_text()
            _require(compiled_pallas or not on_tpu,
                     f"{name}: no Pallas custom call in the lowered text")
            t0 = time.monotonic()
            got = jax.block_until_ready(lowered.compile()(*args))
            seconds = time.monotonic() - t0
            want = jax.block_until_ready(jax.jit(ref_fn)(*args))
            errs = {}
            for label, g, w in zip(name.split("/")[1].split(","), got, want):
                g, w = g.astype(jnp.float32), w.astype(jnp.float32)
                _require(bool(jnp.isfinite(g).all()),
                         f"{name}: {label} is not finite")
                err = float(jnp.abs(g - w).max() / jnp.abs(w).max())
                errs[label] = round(err, 6)
                _require(err <= rel_tol,
                         f"{name}: {label} is off by {err:.4g} of "
                         f"max|reference| (allowed {rel_tol:.4g})")
            results.append({"kernel": name, "rel_err": errs,
                            "compiled_pallas": compiled_pallas,
                            "compile_and_run_s": round(seconds, 2)})
    finally:
        fa._INTERPRET, fa._STREAM_KV_ELEMS, fa._STREAM_QDO_ELEMS = saved
    return results


def train_loop(config: dict) -> None:
    """train_loop_per_worker of the smoke's JaxTrainer run."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig, make_train_step
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.tpu.accelerator import granted_chips

    p, t_start = config["train"], time.monotonic()
    n = len(granted_chips())
    devices = jax.devices()[:n]
    widths = {f: p[f] for f in ("vocab_size", "dim", "n_heads", "n_kv_heads")
              if f in p}
    cfg = getattr(LlamaConfig, p["model"])(
        n_layers=p["n_layers"], max_seq_len=p["seq"], attention_impl="flash",
        dtype=getattr(jnp, p["dtype"]),
        param_dtype=getattr(jnp, p["param_dtype"]), **widths)
    mesh = MeshSpec(fsdp=n).build(devices)
    init_state, shard_state, step, data_sharding = make_train_step(
        cfg, mesh, remat="dots")
    state = shard_state(init_state(jax.random.key(0)))
    batch = p["batch_per_chip"] * n
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (batch, p["seq"]), 0,
                           cfg.vocab_size, dtype=jnp.int32), data_sharding)

    # check the lowered text for the Pallas call: the config string proves
    # nothing about what was compiled
    lowered = step.lower(state, tokens)
    pallas_in_step = "tpu_custom_call" in lowered.as_text()
    on_tpu = devices[0].platform == "tpu"
    _require(pallas_in_step or not on_tpu,
             "attention_impl='flash' but the lowered train step has no "
             "Pallas custom call")
    compiled = lowered.compile()

    params = state[0]
    per_device = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes)
    if n > 1:
        lo, hi = min(per_device.values()), max(per_device.values())
        _require(len(per_device) == n and hi <= 1.1 * lo,
                 f"parameters are not spread over the mesh: {per_device}")

    losses, step_s = [], []
    for i in range(p["steps"]):
        t0 = time.monotonic()
        state, loss = compiled(state, tokens)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(time.monotonic() - t0)
        if i == 0:
            first_step_s = time.monotonic() - t_start
    _require(all(jnp.isfinite(jnp.asarray(losses))), f"loss: {losses}")
    # step 0 is the warm-up; the fixed batch must be fitted better after
    # the steps that follow it
    _require(len(losses) >= 6 and losses[-1] < losses[1],
             f"loss did not fall over {len(losses) - 1} steps: {losses}")
    memory = devices[0].memory_stats() or {}
    del state, params, compiled

    kernels = kernel_checks(config["kernels"])
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(jax.devices()),
        "mesh": {"fsdp": n},
        "model_cut": {**{f: getattr(cfg, f) for f in (
            "vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads",
            "ffn_dim")}, "params": cfg.num_params(), "seq": p["seq"],
            "batch": batch, "remat": "dots", "attention_impl": "flash",
            "note": "one chip's quarter share of the 128,256 vocabulary "
                    "rows, 2 of 32 layers" if p["model"] == "llama3_8b"
                    else "test preset"},
        "pallas_in_step": pallas_in_step,
        "losses": [round(x, 4) for x in losses],
        "param_bytes_per_device": per_device,
        "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
        "kernels": kernels,
        "smoke_timings_s": {
            "start_to_first_step": round(first_step_s, 2),
            "steady_step": round(sorted(step_s[1:])[len(step_s[1:]) // 2], 4),
        },
    })


def train_phase(chips: int, preset: dict) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t_start = time.monotonic()
    trainer = JaxTrainer(
        train_loop, train_loop_config=preset,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="chip-smoke",
                             storage_path=os.path.join(OUT_DIR, "train")))
    got = trainer.scaling_config.resources_per_worker
    _require(got == {"TPU": float(chips)},
             f"JaxTrainer sized its worker {got} on a {chips}-chip host")
    metrics = trainer.fit().metrics
    # (the CPU preset's worker sees every virtual CPU device, not its grant)
    _require(metrics["mesh"] == {"fsdp": chips} and (
        metrics["platform"] != "tpu" or metrics["device_count"] == chips),
        f"train worker: mesh {metrics['mesh']}, "
        f"{metrics['device_count']} devices, {chips} chips granted")
    metrics["smoke_timings_s"]["phase_total"] = round(
        time.monotonic() - t_start, 2)
    return {"phase": "train", "ok": True, **metrics}


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

PHASES = {"serve": serve_phase, "train": train_phase}


def _tail_logs(session_dir: str, n_bytes: int = 6000) -> None:
    """The chip tool shows only the end of the output: put the ends of the
    worker and daemon logs there, and the whole files in chiprun_out/."""
    logs = sorted(glob.glob(os.path.join(session_dir, "logs", "*")),
                  key=os.path.getmtime)
    os.makedirs(os.path.join(OUT_DIR, "logs"), exist_ok=True)
    for path in logs:
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        with open(os.path.join(OUT_DIR, "logs", os.path.basename(path)),
                  "wb") as f:
            f.write(data[-2_000_000:])
        name = os.path.basename(path)
        if data.strip() and (name.endswith(".err") or name.startswith("daemon")):
            print(f"----- tail of {name} -----\n"
                  f"{data[-n_bytes:].decode('utf-8', 'replace')}",
                  file=sys.stderr)


def run_phase(name: str, preset: dict, init_kwargs: dict) -> dict:
    """One phase on a cluster of its own, so the chips its processes held
    are free again for the next phase."""
    import ray_tpu

    info = ray_tpu.init(**init_kwargs)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips < 1:
            raise SystemExit(
                "chip_smoke: no TPU: ray_tpu.cluster_resources() has no "
                f"'TPU' entry ({ray_tpu.cluster_resources()}); the node "
                "daemon found no chip device files on this host")
        result = PHASES[name](chips, preset)
        _require(result["platform"] == "tpu" or preset is TINY,
                 f"phase {name} ran on {result['platform']}, not on TPU chips")
        print(json.dumps(result), flush=True)
        return result
    except BaseException:
        _tail_logs(info["session_dir"])
        raise
    finally:
        if name == "serve":
            from ray_tpu import serve

            try:
                serve.shutdown()
            except Exception:  # noqa: BLE001 — teardown after a failed phase
                traceback.print_exc()
        ray_tpu.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=["serve", "train", "both"],
                    default="both")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="full",
                    help="test-only: 'tiny' runs the phases on a CPU-pinned "
                         "cluster with fake TPU resources")
    args = ap.parse_args(argv)
    preset = PRESETS[args.preset]
    init_kwargs = {}
    if args.preset == "tiny":
        # fake chips on a CPU-pinned cluster; the train worker's fsdp mesh
        # needs as many (virtual) CPU devices as it was granted chips
        init_kwargs = {"num_cpus": 8, "resources": {"TPU": 2}}
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    names = ["serve", "train"] if args.phase == "both" else [args.phase]
    results = {}

    def out_of_time(_sig, _frame):
        raise TimeoutError(
            f"chip_smoke ran past its {DEADLINE_S}s deadline (the chip "
            "check allows 1200s, compilation included)")

    # a hung phase must end as a failure with its logs, not as a killed job
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(DEADLINE_S)
    try:
        for name in names:
            results[name] = run_phase(name, preset, init_kwargs)
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 — any failed phase fails the run
        traceback.print_exc()
        print(f"chip_smoke: FAILED in phase {name!r}", file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
    # this process must have stayed off the chip from start to finish
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("chip_smoke: FAILED: the driver process initialised a JAX "
                  "backend", file=sys.stderr)
            return 1
    print(json.dumps({
        "summary": "chip_smoke", "phases": {n: "ok" for n in names},
        "preset": args.preset, "driver_jax_backend_initialised": False,
        "claim": None,
    }), flush=True)
    # the result line the chip check reads: exactly these keys, the device as
    # JAX reported it to the process(es) that held the chips in the last phase
    last = results[names[-1]]
    print(json.dumps({
        "ok": True,
        "device": {"platform": str(last["platform"]),
                   "kind": str(last["device_kind"]),
                   "count": int(last["device_count"])},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
