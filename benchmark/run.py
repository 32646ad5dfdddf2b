#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data, found by name:

    workloads/<cell>.json     config, traffic, chips, the metrics it reports
    configs/<config>.json     published sizes, the cut, runner, engine or job
    traffic/<traffic>.json    the generator's name and its parameters
    traffic/<generator>.py    seed + parameters -> requests or batches
    runners/<runner>.py       drives the system under test through its entry point
    layer_metrics/<name>.py   one reader per per-layer metric

The last line of stdout is the result: one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with --trace 1),
then `problems` (why the run is not `correct`, each text with the numbers it
was held to; empty when it is) and, last, `held` (each number compared beside
its limit, under a short name, in every run of a serve cell). The same two
are the last lines of stderr.
With --trace 0 the metrics are the cell's end-to-end metrics; with --trace 1
its per-layer metrics, from a run with tracing and a profiler window on.
Progress goes to stderr. Without a TPU (or with fewer chips than the cell
asks for) the exit code is not 0 and no result is printed; a cell marked
`"rehearsal": true` (CPU tests only, not in BENCHMARK.json) runs on the CPU
and prints counts but no metric.

This process never initialises a JAX backend: the chips belong to the
processes the program starts.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")
EXIT_NO_CHIP = 3
TRACE_SKIP_HEAD_S = 0.3  # the profiler's own start-up, see lib/xplane.reduce


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Context:
    """What a runner gets: the cell's data and the harness's services."""

    def __init__(self, args, cell, config, traffic, generator, cache_dir):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.generator, self.cache_dir = generator, cache_dir
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.rehearsal = bool(cell.get("rehearsal"))
        self.out_dir = os.path.join(OUT_DIR, args.workload)
        self.trace_dir = os.path.join(self.out_dir, "trace")

    def log(self, msg: str) -> None:
        print(f"[bench {self.elapsed():7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def elapsed(self) -> float:
        """Seconds since this process started: what a run's time limit
        counts."""
        return time.monotonic() - T_START

    def cache_files(self) -> int:
        """Entries in the persistent compile cache: one more after the
        window opened means something compiled inside it."""
        from benchmark.lib import stats

        return stats.cache_files(self.cache_dir)

    def check_devices(self, seen) -> dict:
        """`seen`: (platform, device_kind, device_count) of each process that
        holds chips. One platform and kind, as many chips as the cell asks
        for, and a TPU unless this is a rehearsal."""
        platforms = {s[0] for s in seen}
        kinds = {s[1] for s in seen}
        if len(platforms) != 1 or len(kinds) != 1:
            raise SystemExit(f"mixed devices: {seen}")
        platform = platforms.pop()
        count = sum(s[2] for s in seen)
        if platform != "tpu" and not self.rehearsal:
            print(f"benchmark: the program runs on {platform!r}, not on TPU "
                  "chips", file=sys.stderr)
            raise SystemExit(EXIT_NO_CHIP)
        if platform == "tpu" and count != int(self.cell["chips"]):
            raise SystemExit(
                f"the cell asks for {self.cell['chips']} chips, the program "
                f"holds {count}")
        if self.rehearsal:
            count = int(self.cell["chips"])
        return {"platform": platform, "kind": kinds.pop(), "count": count}


def reduce_trace(ctx: Context, art: dict) -> None:
    """The traced run's `.xplane.pb` to busy time, operations and gaps. On
    the chip a trace without a device plane is an error (no device metric
    could be told from a missing one); the CPU rehearsal has none."""
    from benchmark.lib import xplane

    call = art.get("trace_call")
    path = xplane.find_xplane(call["logdir"]) if call else None
    try:
        if path is None:
            raise ValueError("the traced run wrote no .xplane.pb")
        ctx.log(f"reading {path} ({os.path.getsize(path) / 1e6:.1f} MB)")
        art["trace"] = xplane.reduce(xplane.load(path),
                                     skip_head_s=TRACE_SKIP_HEAD_S)
        ctx.log("trace reduced")
    except ValueError as e:
        if not ctx.rehearsal:
            raise
        ctx.log(f"no device trace: {e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_json("workloads", f"{args.workload}.json")
    config = load_json("configs", f"{cell['config']}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    sys.path.insert(0, ROOT)
    # the processes the program starts import the benchmark's modules too
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])
    # one fixed compile cache inside the checkout unless the machine gives
    # one; every compilation is written there, so that counting its files
    # sees any compilation
    cache_dir = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.makedirs(cache_dir, exist_ok=True)

    generator = importlib.import_module(
        f"benchmark.traffic.{traffic['generator']}")
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    ctx = Context(args, cell, config, traffic, generator, cache_dir)
    shutil.rmtree(ctx.out_dir, ignore_errors=True)
    os.makedirs(ctx.out_dir, exist_ok=True)

    import ray_tpu

    init_kwargs = {}
    if ctx.rehearsal:
        # fake chips on a CPU-pinned cluster; a mesh over several needs as
        # many virtual CPU devices
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        init_kwargs = {"num_cpus": 8,
                       "resources": {"TPU": int(cell["chips"])}}
    if ctx.trace:
        init_kwargs["system_config"] = {"tracing_enabled": True}
    info = ray_tpu.init(**init_kwargs)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips < int(cell["chips"]):
            print(f"benchmark: the cell asks for {cell['chips']} chips, this "
                  f"host advertises {chips}", file=sys.stderr)
            return EXIT_NO_CHIP
        art = runner.run(ctx)
        ctx.log("the runner returned")
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 — any failure fails the run, with its logs
        traceback.print_exc()
        _tail_logs(info.get("session_dir"))
        return 1
    finally:
        # no serve.shutdown(): its proxy drain waits out its 30 s timeout on
        # the requests the load generator abandoned at the close, in every
        # run. ray_tpu.shutdown() ends the daemon's whole process group,
        # serve's actors included.
        ray_tpu.shutdown()
        ctx.log("the cluster is down")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("benchmark: the driver process initialised a JAX backend",
                  file=sys.stderr)
            return 1

    # a run that measured a second window (runners/serve_dp.py) reports the
    # first opening: a retry cannot move the set-up time
    art["setup_s"] = art.get("first_t_open", art["t_open"]) - T_START
    on_chip = art["device"]["platform"] == "tpu"
    device = dict(art["device"])
    device["memory_peak_bytes"] = art.get("memory_peak_bytes")
    metrics, breakdown = {}, None
    if ctx.trace:
        reduce_trace(ctx, art)
        from benchmark import layer_metrics

        for name in cell["per_layer"]:
            mod = layer_metrics.load(name)
            value = mod.read(art)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        if art.get("trace"):
            device["busy_s"] = art["trace"]["busy_s"]
            device["window_s"] = art["trace"]["window_s"]
            breakdown = {"device_ops": art["trace"]["device_ops"],
                         "idle_gaps": art["trace"]["idle_gaps"]}
    else:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            units = {m["name"]: m["unit"] for m in json.load(f)["end_to_end"]}
        values = {**art["end_to_end"], "setup_s": art["setup_s"]}
        for name in cell["end_to_end"]:
            if values.get(name) is None:
                art["problems"].append(f"no value for {name}")
                continue
            metrics[name] = {"value": values[name], "unit": units[name]}
    summary = {k: art.get(k) for k in ("check", "engine_init_s", "flash_bound",
                                       "stats_open", "stats_close", "load",
                                       "counter_tokens_per_s", "retried",
                                       "close_read_late_s", "close_read_took_s",
                                       "count_covers_s",
                                       "capture_returned_after_close_s")}
    if art.get("gen_late_s"):
        summary["gen_late_max_ms"] = max(art["gen_late_s"]) * 1e3
    summary["trace"] = {k: v for k, v in (art.get("trace") or {}).items()
                        if isinstance(v, (int, float))}
    summary["requests_or_steps"] = art["attempted"]
    summary["end_to_end"] = {**art["end_to_end"], "setup_s": art["setup_s"]}
    ctx.log(f"summary: {json.dumps(summary, default=str)}")
    if not on_chip:
        # a CPU run says what was counted and whether it was right, never a
        # rate under a device metric's name
        ctx.log(f"rehearsal metrics (not reported): {json.dumps(metrics)}")
        metrics = {}
    # why and by what numbers, last on stderr: the driver's record of a run
    # that is not correct keeps the end of it and nothing else
    held = f" [held to: {art['held']}]" if art.get("held") else ""
    problems = [f"{p}{held}" for p in art["problems"]]
    if not problems and held:
        ctx.log(f"held to: {art['held']}")
    for p in problems:
        ctx.log(f"NOT CORRECT: {p}")
    result = {"correct": not problems, "attempted": art["attempted"],
              "failed": art["failed"], "metrics": metrics, "device": device}
    if breakdown is not None and on_chip:
        result["breakdown"] = breakdown
    result["problems"] = problems
    result["held"] = art.get("held_numbers", {})
    print(json.dumps(result), flush=True)
    return 0


def _tail_logs(session_dir, n_bytes: int = 4000) -> None:
    """The chip tool shows only the end of the output: put the ends of the
    workers' error logs there."""
    import glob

    if not session_dir:
        return
    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "*.err")),
                       key=os.path.getmtime)[-6:]:
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        if data.strip():
            print(f"----- tail of {os.path.basename(path)} -----\n"
                  f"{data[-n_bytes:].decode('utf-8', 'replace')}",
                  file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
