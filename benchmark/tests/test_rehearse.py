"""One --rehearse run of each driver, end to end on the CPU: the last line
of standard output has exactly the contract's keys. A rehearsal names the
CPU and is never a result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = [("gpt2-medium.decode-batch", 0, 6), ("gpt2-medium.prefix-chat", 1, 6),
         ("gpt2-medium.pretrain-1k", 0, 2), ("gpt2-xl.pretrain-1k", 1, 2)]


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


@pytest.mark.parametrize("cell,trace,seconds", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace, seconds):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 11), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=_env(), capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"            # never a chip result
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in man[kind] if cell in m.get("workloads", [cell])}
    assert last["metrics"] and set(last["metrics"]) <= allowed
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if not trace:
        assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2


def test_without_a_chip_the_command_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-medium.pretrain-1k",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env={**_env(), "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
