"""Runner `train_jax`: the trained path as a user calls it.

    JaxTrainer(use_tpu) -> one worker holding the cell's chips ->
    models.llama.make_train_step on an fsdp mesh, flash kernels

`train_loop` is the user's training function and the benchmark's own code: it
draws the batches, keeps the step clock, takes the trace and holds the first
step against the plain reference, all in the process that holds the chips.
This process (the driver) never initialises a JAX backend.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from benchmark.lib.config import CellFailure, model_overrides, published
from benchmark.runners import _inside

ADAM_B1 = 0.9  # optax.adamw's default, which make_train_step uses
# The step computes in bf16 with float32 weights; the reference in float32
# throughout. At initialisation the loss is ~ln(vocabulary) = 11.4 and a bf16
# forward pass moves it by a few 1e-3 absolute (measured on v5e, PR 22: 2.3e-4
# relative); the gradient norm sums 1.9e9 bf16-rounded products (measured:
# 0.11% apart). Allowed: 0.2% on the loss and 1% on the norm, nine times what
# was measured. A dropped layer, a wrong mask or a wrong loss scale moves
# either by tens of percent; 8-bit matmuls, 32 times coarser than bf16, would
# move the norm by more than 1%.
LOSS_REL_TOL = 2e-3
GRAD_NORM_REL_TOL = 1e-2


def state_shardings(state_shapes, param_shardings, mesh):
    """A sharding for every leaf of (params, opt_state): a parameter's own,
    and for an optimizer moment that of the parameter whose key path ends its
    own (the rule of parallel.mesh.shard_train_state, which places a state
    that already exists; this builds it in place). `shard_state` under `jit`
    leaves the state replicated: 22.7 GB a chip (v5e compile, PR 22)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from jax.tree_util import keystr, tree_flatten_with_path

    by_path = {keystr(path): s for (path, _), s in zip(
        tree_flatten_with_path(state_shapes[0])[0],
        jax.tree.leaves(param_shardings))}
    replicated = NamedSharding(mesh, PartitionSpec())

    def pick(path, leaf):
        ks = keystr(path)
        for pk, s in by_path.items():
            if ks.endswith(pk) and leaf.ndim == len(s.spec):
                return s
        return replicated

    return jax.tree_util.tree_map_with_path(pick, state_shapes)


def train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: runs inside the train worker."""
    import importlib

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import (LlamaConfig, init_params,
                                      make_train_step, param_specs)
    from ray_tpu.parallel.mesh import MeshSpec, logical_to_sharding

    from benchmark.lib import reference, stats

    cfg_file, traffic = config["config"], config["traffic"]
    # `--seed` may be a little over 2**31 and reaches the jitted `make_batch`
    # as an int32 (OverflowError): folded into 31 bits here, once, for the
    # weights' key too; a seed under 2**31 is itself
    seed = int(config["seed"]) % 2 ** 31
    seconds, n = config["seconds"], config["chips"]
    generator = importlib.import_module(
        f"benchmark.traffic.{traffic['generator']}")
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise CellFailure(f"{len(devices)} devices for {n} chips")
    hp = published(cfg_file)
    cfg = LlamaConfig(**model_overrides(cfg_file))
    mesh = MeshSpec(fsdp=n).build(devices)
    init_state, shard_state, step, data_sharding = make_train_step(
        cfg, mesh, remat=traffic["remat"])
    key = jax.random.key(seed)
    make_batch = jax.jit(generator.batch_fn(traffic, cfg.vocab_size, n),
                         out_shardings=data_sharding)
    tokens_per_step = generator.tokens_per_step(traffic, n)

    # 1. the reference, before the train state exists: float32 loss and
    #    gradient norm of one sequence on weights made from the same key
    check_row = np.asarray(make_batch(seed, 2 ** 30))[0]
    shardings = logical_to_sharding(param_specs(cfg), mesh)
    ref_params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=shardings)(key)
    want = reference.loss_and_grad_norm(
        hp, _inside.ProgramWeights(ref_params, devices[0]), check_row)
    del ref_params

    # 2. the state, built already sharded (init_state alone would put all of
    #    it on device 0), and the first step on that sequence in every row
    state = jax.jit(init_state, out_shardings=state_shardings(
        jax.eval_shape(init_state, key), shardings, mesh))(key)
    check_batch = jax.device_put(
        np.tile(check_row[None], (make_batch(seed, 0).shape[0], 1)),
        data_sharding)
    state, loss = step(state, check_batch)
    got_loss = float(jax.block_until_ready(loss))
    got_norm = float(jax.jit(optax.global_norm)(state[1][0].mu)) / (1 - ADAM_B1)
    check = {
        "loss": got_loss, "ref_loss": want["loss"],
        "grad_norm": got_norm, "ref_grad_norm": want["grad_norm"],
        "loss_rel": abs(got_loss - want["loss"]) / abs(want["loss"]),
        "grad_norm_rel": abs(got_norm - want["grad_norm"]) / want["grad_norm"],
    }
    check["ok"] = bool(check["loss_rel"] <= LOSS_REL_TOL
                       and check["grad_norm_rel"] <= GRAD_NORM_REL_TOL)

    # 3. warm every program of the window, then open it at a step boundary
    for i in range(int(traffic["warmup_steps"])):
        state, loss = step(state, make_batch(seed, 2 ** 30 + 1 + i))
        jax.block_until_ready(loss)
    train.report({"step": -1})
    cache_files_open = stats.cache_files(config["cache_dir"])
    report_every = int(traffic["report_every"])
    trace_steps = (int(traffic["trace_first_step"]),
                   int(traffic["trace_steps"])) if config["trace"] else None
    done, losses, report_s, traced = [], [], [], set()
    trace_info = None
    t_open = time.monotonic()
    i = 0
    while True:
        if trace_steps and i == trace_steps[0]:
            jax.profiler.start_trace(config["trace_dir"],
                                     profiler_options=_inside.profile_options())
        state, loss = step(state, make_batch(seed, i))
        losses.append(float(jax.block_until_ready(loss)))
        done.append(time.monotonic())
        if trace_steps and trace_steps[0] <= i < sum(trace_steps):
            traced.add(i)
            if i == sum(trace_steps) - 1:
                jax.profiler.stop_trace()
                trace_info = {"logdir": config["trace_dir"]}
                # the stop writes the file: not a step's time
                traced.add(i + 1)
        if (i + 1) % report_every == 0:
            t0 = time.monotonic()
            train.report({"step": i, "loss": losses[-1]})
            report_s.append(time.monotonic() - t0)
        i += 1
        if done[-1] - t_open >= seconds:
            break
    cache_files_close = stats.cache_files(config["cache_dir"])
    memory = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    memory = [m for m in memory if m is not None]
    step_s = np.diff([t_open] + done).tolist()
    train.report({
        "final": True,
        "platform": devices[0].platform, "device_kind": devices[0].device_kind,
        "device_count": len(jax.devices()),
        "t_open": t_open, "done": done, "step_s": step_s,
        "traced_steps": sorted(traced), "losses": losses,
        "report_s": report_s, "tokens_per_step": tokens_per_step,
        "check": check, "trace_call": trace_info,
        "cache_files_open": cache_files_open,
        "cache_files_close": cache_files_close,
        "memory_peak_bytes": max(memory) if memory else None,
        "pallas_in_step": "tpu_custom_call" in step.lower(
            state, check_batch).as_text(),
    })


def run(ctx) -> Dict[str, Any]:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, traffic = ctx.cell, ctx.traffic
    chips = int(cell["chips"])
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "config": ctx.config, "traffic": traffic, "seed": ctx.seed,
            "seconds": float(ctx.seconds), "chips": chips,
            "trace": ctx.trace, "trace_dir": ctx.trace_dir,
            "cache_dir": ctx.cache_dir},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"TPU": float(chips)}),
        run_config=RunConfig(name="bench",
                             storage_path=os.path.join(ctx.out_dir, "train")))
    m = trainer.fit().metrics
    if not m.get("final"):
        raise CellFailure(f"the train loop did not report its result: {m}")
    art: Dict[str, Any] = dict(m)
    art["device"] = ctx.check_devices(
        [(m["platform"], m["device_kind"], m["device_count"])])
    art["window_s"] = m["done"][-1] - m["t_open"]
    art["chips"] = chips
    art["hp"], art["seq_len"] = published(ctx.config), int(traffic["seq_len"])
    steps = len(m["done"])
    art["end_to_end"] = {
        "train_tokens_per_s": m["tokens_per_step"] * steps / art["window_s"]}
    losses, problems = m["losses"], []
    if not m["check"]["ok"]:
        problems.append(f"reference check failed: {m['check']}")
    if not all(x == x and abs(x) != float("inf") for x in losses):
        problems.append(f"loss not finite: {losses}")
    k = min(10, len(losses) // 2)
    if k < 1 or sum(losses[-k:]) >= sum(losses[:k]):
        problems.append(f"the loss did not fall over {len(losses)} steps: "
                        f"first {losses[:k]}, last {losses[-k:]}")
    if m["cache_files_open"] != m["cache_files_close"]:
        problems.append(
            f"compiled inside the window: {m['cache_files_open']} -> "
            f"{m['cache_files_close']} files in the compile cache")
    if art["device"]["platform"] == "tpu" and not m["pallas_in_step"]:
        problems.append("attention_impl='flash' but no Pallas call in the step")
    art["problems"] = problems
    art["attempted"], art["failed"] = steps, 0
    ctx.log(f"reference check: {m['check']}")
    return art
