"""benchmark/run.py end to end on the CPU at a tiny configuration kept for
that purpose: cells, configurations and traffic files that BENCHMARK.json
does not list and that were added the way a later PR adds a cell, as new
files only. Also: BENCHMARK.json agrees with the files it names.

    python -m pytest benchmark/tests -q        (not part of tier-1)
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
# the contract's five and, last, why a run is not `correct` and every number
# it was held to beside its limit (PR 58; the driver ignores keys it does
# not read)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "problems", "held"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def run_cell(cell, trace, seconds=5, seed=5, script="run.py"):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    return proc


@pytest.mark.parametrize("cell,trace,seed", [
    ("tiny-chat-open", 0, 5), ("tiny-docqa-closed", 1, 5),
    ("tiny-pretrain-fsdp4", 0, 5),
    # the driver's seeds go a little over 2**31, more than an int32 holds
    ("tiny-pretrain-fsdp4", 0, 2303000001), ("tiny-chat-open", 0, 2 ** 31 + 11)])
def test_tiny_cell_runs_end_to_end_on_the_cpu(cell, trace, seed):
    proc = run_cell(cell, trace, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    result = json.loads(last)
    # exactly the contract's keys (no breakdown: no device was traced)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["problems"] == [] and list(result)[-2:] == ["problems", "held"]
    if cell != "tiny-pretrain-fsdp4":
        assert result["held"]["requests_failed"] == {"value": 0, "limit": 0}
        assert proc.stderr.strip().splitlines()[-1].split("] ", 1)[1].startswith(
            "held to: 0 of ")
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == load("workloads", f"{cell}.json")["chips"]
    # a CPU run prints counts and `correct`, never a rate, a time or a
    # utilization under a device metric's name
    assert result["metrics"] == {}
    assert "reference check" in proc.stderr


@pytest.mark.parametrize("cell,trace", [
    ("tiny-chat-open", 1), ("tiny-docqa-closed", 0)])
def test_a_host_fault_in_the_first_window_is_followed_by_one_more(cell, trace):
    """tests/forced_retry.py marks the first window as a stalled host would:
    the run ramps again on the engine that is up, measures and judges a
    second window, and reports it with the first opening as its set-up."""
    proc = run_cell(cell, trace, script=os.path.join("tests", "forced_retry.py"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    log = [ln for ln in proc.stderr.splitlines() if ln.startswith("[bench")]
    again = [ln for ln in log if "one more window" in ln]
    assert len(again) == 1 and any("the engine settled" in ln for ln in log)
    assert sum("reference check" in ln for ln in log) == 1
    summary = json.loads(next(
        ln for ln in log if "summary: " in ln).split("summary: ", 1)[1])
    assert "forced by tests/forced_retry.py" in summary["retried"]
    # set-up ended before the first window, a whole window before the second
    second_ramp_at = float(again[0][len("[bench"):].split("s]")[0])
    assert summary["end_to_end"]["setup_s"] < second_ramp_at - 5.0
    if trace:
        assert sum("tracing the engine's process" in ln for ln in log) == 2


def test_a_run_that_is_not_correct_ends_with_its_reasons():
    """tests/forced_fault.py plants a fault of the system's: no second
    window, exit code 0, `correct` false, and the reasons with every number
    held beside its limit are the last lines of stderr and the result's
    `problems`: the driver's record keeps the end of each and nothing else."""
    proc = run_cell("tiny-docqa-closed", 0,
                    script=os.path.join("tests", "forced_fault.py"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS and result["correct"] is False
    assert list(result)[-2:] == ["problems", "held"]
    assert len(result["problems"]) == 1
    assert "planted by tests/forced_fault.py [held to: 0 of " in result["problems"][0]
    assert "client's rate / engine's count - 1 = " in result["problems"][0]
    assert result["held"]["client_rate_over_engine_count_minus_1"]["limit"] == 0.5
    log = [ln for ln in proc.stderr.splitlines() if ln.startswith("[bench")]
    assert "summary: " in log[-3] and "rehearsal metrics" in log[-2]
    assert log[-1].split("] ", 1)[1] == "NOT CORRECT: " + result["problems"][0]
    assert not any("one more window" in ln for ln in log)


def test_a_real_cell_refuses_to_run_without_a_chip():
    proc = run_cell("mistral7b-chat-closed", 0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_json_agrees_with_the_files_it_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    four_chip = 0
    for w in bench["workloads"]:
        cell = load("workloads", f"{w['name']}.json")
        assert not cell.get("rehearsal")
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert len(w["why"]) <= 200
        four_chip += w["chips"] == 4
        cfg = load("configs", f"{w['config']}.json")
        assert configs[w["config"]]["file"] == f"benchmark/configs/{w['config']}.json"
        assert configs[w["config"]]["source"] == cfg["source"]
        assert sorted(configs[w["config"]]["reduced"]) == sorted(cfg["reduced"])
        traffic = load("traffic", f"{w['traffic']}.json")
        importlib.import_module(f"benchmark.traffic.{traffic['generator']}")
        importlib.import_module(f"benchmark.runners.{cfg['runner']}")
        # every metric the cell reports is declared for it, and a per-layer
        # metric sits beside the end-to-end metric it moves
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
        for name in cell["end_to_end"]:
            assert w["name"] in e2e[name].get("workloads", [w["name"]])
        for name in cell["per_layer"]:
            m, mod = per_layer[name], importlib.import_module(
                f"benchmark.layer_metrics.{name}")
            assert w["name"] in m.get("workloads", [w["name"]])
            assert (m["unit"], m["layer"], m["source"], m["moves"]) == (
                mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES)
            assert m["moves"] in cell["end_to_end"]
    for m in list(e2e.values()) + list(per_layer.values()):
        for w in m.get("workloads", []):
            cell = load("workloads", f"{w}.json")
            assert m["name"] in cell["end_to_end"] + cell["per_layer"]
    assert four_chip <= max(1, len(bench["workloads"]) // 4)
    assert all(0 < m["bound"] <= 0.1 for m in bench["end_to_end"])


def test_published_widths_are_unchanged():
    """The two configurations as their sources give them; only depth may be
    cut, and only where `reduced` says so."""
    mistral = load("configs", "mistral-7b-v0.3-l16.json")
    assert {k: mistral[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps")} == {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "vocab_size": 32768, "rope_theta": 1e6, "rms_norm_eps": 1e-5}
    assert list(mistral["reduced"]) == ["num_hidden_layers"]
    assert mistral["reduced"]["num_hidden_layers"]["published"] == 32
    assert mistral["num_hidden_layers"] == mistral["reduced"]["num_hidden_layers"]["here"]
    intern = load("configs", "internlm2-1.8b.json")
    assert {k: intern[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size", "rope_theta",
        "rms_norm_eps")} == {
        "hidden_size": 2048, "intermediate_size": 8192,
        "num_attention_heads": 16, "num_key_value_heads": 8,
        "num_hidden_layers": 24, "vocab_size": 92544, "rope_theta": 1e6,
        "rms_norm_eps": 1e-5}
    assert intern["reduced"] == {}
