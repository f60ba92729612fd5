"""Each cell's job at a tiny size on the CPU: sound runs come out correct,
its control does not, and a run with a fault planted under the timed path
comes out not correct. The four-executor sort (traffic ``is-sort.x4``) runs
in a child process with four virtual CPU devices, whether or not
BENCHMARK.json has a cell for it yet."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import control, harness, planted

TINY = {"tokens": 1 << 14, "vocab": 256, "n": 1 << 12, "keys": 1 << 14,
        "max_key": 1 << 10}
SEED = 2**31 + 2**33 + 17
ONE_CHIP = ["wordcount-zipf.1chip", "fig12-cg.1chip", "npb-is-c.1chip"]
FOUR_CHIP = "npb-is-c.4chip"


def _spec() -> dict:
    """BENCHMARK.json, with the four-executor IS cell added if it is absent."""
    spec = harness.load_spec()
    if FOUR_CHIP not in [w["name"] for w in spec["workloads"]]:
        spec["workloads"].append({"name": FOUR_CHIP, "config": "npb-is-c",
                                  "traffic": "is-sort.x4", "chips": 4, "why": "-"})
    return spec


def _argv(cell, trace=0, seed=SEED):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "0.3",
            "--trace", str(trace)]


def _run(cell, trace=0, seed=SEED):
    return harness.run(_argv(cell, trace, seed), require_tpu=False,
                       config_overrides=TINY)


def _four(fault=None, trace=0):
    """Run the four-chip cell in a child with four CPU devices; its result."""
    code = (
        "import contextlib, sys\n"
        "from benchmarks.chip import harness, planted\n"
        f"ctx = planted.FAULTS[{fault!r}]() if {fault!r} else contextlib.nullcontext()\n"
        "with ctx:\n"
        f"    harness.run({_argv(FOUR_CHIP, trace)!r}, require_tpu=False,\n"
        f"                config_overrides={TINY!r}, spec={_spec()!r})\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT, os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=harness.ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) >= {"setup_s"} and len(r["metrics"]) >= 2
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1


def test_traced_run_reports_per_layer_metrics():
    r = _run("wordcount-zipf.1chip", trace=1)
    assert r["correct"]
    assert r["metrics"]["window_compiles.dataflow"]["value"] == 0
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def test_four_chip_run_is_correct():
    r = _four()
    assert r["correct"] and r["device"]["count"] == 4


@pytest.mark.parametrize("cell", ONE_CHIP + [FOUR_CHIP])
def test_control_fails(cell):
    r = control.readings(cell, SEED + 1, config_overrides=TINY, spec=_spec())
    assert not r["correct"], r


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_planted_fault_is_not_correct(cell, fault):
    with planted.FAULTS[fault]():
        r = _run(cell)
    assert not r["correct"], r


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer", "no_exchange"])
def test_planted_fault_is_not_correct_on_four_chips(fault):
    assert not _four(fault)["correct"]


def test_command_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable] + harness.load_spec()["command"][1:] + _argv("npb-is-c.1chip")
    p = subprocess.run(cmd, cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
    # nor does it run from a directory holding only BENCHMARK.json and the
    # benchmark's own files
    import shutil

    shutil.copy(harness.SPEC_FILE, tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=dict(env, PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
