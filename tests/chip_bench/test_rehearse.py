"""``run.py`` end to end on the CPU: the rehearsal of both runners, the
refusal to measure without a chip, and a cell, a configuration, a mix
and a metric added as new files with no existing file edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from ._paths import BENCH, ROOT, RUN

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(run_py, *argv, cwd=ROOT, extra_env=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
                **(extra_env or {})})
    return subprocess.run([sys.executable, run_py, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace,chips", [
    ("train-125m-zero1", 0, 1),
    ("train-1p3b-zero3-4chip", 1, 4),
    ("serve-1p3b-chat", 1, 1),
    ("serve-1p3b-longprompt", 0, 1),
])
def test_rehearsal_runs_the_cell_and_prints_the_contracts_last_line(
        cell, trace, chips):
    proc = _run(RUN, "--workload", cell, "--seed", str(2 ** 31 + 77),
                "--seconds", "1.5", "--trace", str(trace), "--rehearse")
    line = _last_line(proc)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a CPU run never writes a number under a device metric's name
    assert line["metrics"] == {}
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    assert "set-up phases s:" in proc.stdout and "samples:" in proc.stdout
    assert "reference check:" in proc.stdout


def test_without_a_chip_and_without_rehearse_it_refuses():
    proc = _run(RUN, "--workload", "train-125m-zero1", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_alone_with_its_manifest_it_exits_nonzero_and_prints_no_result(
        tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path / "benchmarks" / "chip" / "run.py"),
                "--workload", "train-125m-zero1", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse",
                cwd=tmp_path, extra_env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_importing_the_benchmark_loads_no_jax_and_describes_no_topology():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks.chip import (manifest, model, opcount, peaks, "
        "phases, reference, serve_runner, stats, tracing, traffic, "
        "train_runner, xplane, readers)\n"
        "readers.load_all()\n"
        "import importlib.util as u\n"
        "spec = u.spec_from_file_location('run', %r)\n"
        "m = u.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "assert 'jax' not in sys.modules, 'jax imported at import time'\n"
        "assert 'libtpu' not in sys.modules\n" % (ROOT, RUN))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


NEW_READER = '''
from . import reader


@reader("steps_counted")
def steps_counted(obs, series):
    """How many host samples the traced run took: a count, so it can be
    read on any platform."""
    return float(len(obs.series.get(series, [])))
'''


def test_a_cell_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """Nothing that is there is edited: the files under ``benchmarks/chip``
    are copied unchanged, four files are added beside them, and
    ``BENCHMARK.json`` gains four entries."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(bench / "configs" / "gpt2-125m-zero1.json") as f:
        config = json.load(f)
    config.update({"n_embd": 1024, "n_layer": 24, "n_head": 16,
                   "source": "https://huggingface.co/openai-community/"
                             "gpt2-medium/blob/main/config.json",
                   "rehearse": dict(config["rehearse"], n_layer=3)})
    (bench / "configs" / "gpt2-350m-zero1.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "steps-seq512-micro16.json").write_text(json.dumps(
        {"kind": "steps", "seq": 512, "micro_per_chip": 16,
         "rehearse": {"seq": 128, "micro_per_chip": 1}}))
    (bench / "metrics" / "train.steps_counted.json").write_text(json.dumps(
        {"name": "train.steps_counted", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "trainer",
         "moves": "train_tokens_per_s_chip",
         "workloads": ["train-350m-zero1"],
         "reader": "steps_counted", "args": {"series": "train_step_ms"}}))
    (bench / "readers" / "counted.py").write_text(NEW_READER)

    manifest["configs"].append(
        {"name": "gpt2-350m-zero1", "source": config["source"],
         "file": "benchmarks/chip/configs/gpt2-350m-zero1.json",
         "reduced": [], "why": "a test's configuration"})
    manifest["workloads"].append(
        {"name": "train-350m-zero1", "config": "gpt2-350m-zero1",
         "traffic": "steps-seq512-micro16", "chips": 1, "why": "a test"})
    manifest["end_to_end"][0]["workloads"].append("train-350m-zero1")
    manifest["per_layer"].append(
        {"name": "train.steps_counted", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "trainer",
         "moves": "train_tokens_per_s_chip",
         "workloads": ["train-350m-zero1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    proc = _run(str(bench / "run.py"), "--workload", "train-350m-zero1",
                "--seed", "5", "--seconds", "1", "--trace", "1",
                "--rehearse", cwd=tmp_path, extra_env={"PYTHONPATH": ROOT})
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] > 0
    assert "train.steps_counted" in proc.stdout     # the new reader ran
    assert "cell train-350m-zero1:" in proc.stdout
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts
             and ".bench_out" not in p.parts}
    assert {p: b for p, b in after.items() if p in before} == before
    assert len(after) == len(before) + 4
