"""``run.py`` end to end on the CPU: the rehearsal of both runners, the
refusal to measure without a chip, and a cell of another model family —
its family, configuration, mix, metrics and reader — added as new files
with no existing file edited, under the benchmark's own tests."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from ._paths import BENCH, PYTHONPATH, ROOT, RUN

KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


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
    # what decided `correct` comes last in the line, each number beside
    # its limit, and the same are the last lines of standard error
    assert list(line)[-1] == "check"
    check = line["check"]
    number = "loss_rms" if cell.startswith("train") else "logit_gap_sigma"
    assert 0 <= check[number]["value"] <= check[number]["limit"]
    assert check["compiles_in_window"] == {"value": 0, "limit": 0}
    assert all(set(n) <= {"value", "limit"} and "value" in n
               for n in check.values())
    if cell.startswith("serve"):
        assert check["tokens_over"] == {"value": 0, "limit": 0}
        assert check["tokens_checked"]["value"] > 0
    said = proc.stderr.strip().splitlines()[-len(check) - 1:]
    assert said[-1] == "correct: true"
    assert said[:-1] == [f"check {name}: {json.dumps(n)}"
                         for name, n in check.items()]


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
        "from benchmarks.chip import (families, manifest, model, opcount, "
        "peaks, phases, reference, serve_runner, stats, tracing, traffic, "
        "train_runner, xplane, readers)\n"
        "readers.load_all()\n"
        "families.load({})\n"
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

NEW_FAMILY = '''
"""A second family, for the test: pre-LN decoder blocks with a ReLU
feed-forward of a width of its own, sizes under the keys of an OPT-style
``config.json``. Its reference is its own."""

from . import MOSAIC
from .. import reference

KEYS = ("hidden_size", "ffn_dim", "num_hidden_layers",
        "num_attention_heads", "max_position_embeddings", "vocab_size")
DEPTH_KEYS = ("num_hidden_layers",)


def sizes(config, rehearse):
    src = config["rehearse"] if rehearse else config
    return {k: src[k] for k in KEYS}


def build(config, rehearse, **overrides):
    import jax.numpy as jnp
    from deepspeed_tpu.models import GPT, GPTConfig
    s = sizes(config, rehearse)
    kw = dict(vocab_size=s["vocab_size"], d_model=s["hidden_size"],
              max_seq_len=s["max_position_embeddings"], d_ff=s["ffn_dim"],
              n_layers=s["num_hidden_layers"], activation="relu",
              n_heads=s["num_attention_heads"], scan_layers=True,
              remat="full", dtype=jnp.bfloat16, param_dtype=jnp.float32)
    kw.update(overrides)
    return GPT(GPTConfig(**kw))


def train_loss(config):
    def loss_fn(model, params, batch, rng, train):
        from deepspeed_tpu.models import gpt_chunked_loss_fn
        ids = batch["input_ids"]
        h, wte = model.apply(params, ids[:, :-1], deterministic=not train,
                             return_hidden=True)
        return gpt_chunked_loss_fn(h, wte, ids[:, 1:],
                                   chunk=config["loss_chunk"])
    return loss_fn


def ops_per_token(sizes, seq):
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    weights = (sizes["num_hidden_layers"] * (4 * d * d + 2 * d * f)
               + sizes["vocab_size"] * d)
    return 6 * weights + sizes["num_hidden_layers"] * 6 * seq * d


def expected_kernels(serving):
    return [("flash_attention", "fwd_", MOSAIC),
            ("flash_attention", "bwd_", MOSAIC)]


def _norm(x, p):
    import jax.numpy as jnp
    x = x - jnp.mean(x, -1, keepdims=True)
    return (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
            * p["scale"] + p["bias"])


def _affine(x, p):
    return x @ p["kernel"] + p["bias"]


def _layer(x, p, heads):
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    q, k, v = (t.reshape(b, s, heads, d // heads) for t in jnp.split(
        _affine(_norm(x, p["ln_1"]), p["attn"]["qkv"]), 3, -1))
    w = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d // heads) ** 0.5
    w = jnp.where(jnp.tril(jnp.ones((s, s), bool)), w, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(w, -1), v)
    x = x + _affine(a.reshape(b, s, d), p["attn"]["out"])
    h = jax.nn.relu(_affine(_norm(x, p["ln_2"]), p["mlp"]["fc_in"]))
    return x + _affine(h, p["mlp"]["fc_out"])


def reference_logits(params, ids, sizes, config):
    import jax
    import jax.numpy as jnp
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["wte"][ids] + p["wpe"][:ids.shape[1]]
    for i in range(sizes["num_hidden_layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[i], p["h"]),
                   sizes["num_attention_heads"])
    return _norm(x, p["ln_f"]) @ p["wte"].T


def reference_next_token_losses(params, ids, sizes, config):
    return reference.next_token_losses(
        reference_logits(params, ids[:, :-1], sizes, config), ids[:, 1:])
'''

PUBLISHED = {"hidden_size": 768, "ffn_dim": 3072, "num_hidden_layers": 12,
             "num_attention_heads": 12, "max_position_embeddings": 2048,
             "vocab_size": 50272}


def _files(*roots):
    return {p: p.read_bytes() for root in roots for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts
            and ".bench_out" not in p.parts}


def test_a_cell_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """Nothing that is there is edited: the files under the benchmark's
    two ``paths`` are copied unchanged, six files are added beside them
    — a family whose size keys are not GPT-2's with its own reference, a
    configuration with a ``published`` block cut in depth, a mix, a
    metric on a ``registry_`` reader, a metric on a new reader and that
    reader — and ``BENCHMARK.json`` gains their entries. The new cell
    rehearses ``correct``, and the copied ``test_manifest.py`` and
    ``test_program_readers.py`` pass on the temporary checkout."""
    bench = tmp_path / "benchmarks" / "chip"
    tests = tmp_path / "tests" / "chip_bench"
    ignore = shutil.ignore_patterns("__pycache__", ".bench_out")
    shutil.copytree(BENCH, bench, ignore=ignore)
    shutil.copytree(os.path.dirname(__file__), tests, ignore=ignore)
    before = _files(bench, tests)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(bench / "configs" / "gpt2-125m-zero1.json") as f:
        config = json.load(f)
    source = "https://huggingface.co/facebook/opt-125m/blob/main/config.json"
    config = {k: v for k, v in config.items()
              if k not in ("n_embd", "n_layer", "n_head", "n_positions",
                           "vocab_size", "rehearse", "assumed")}
    config.update(PUBLISHED, family="reluffn", source=source,
                  num_hidden_layers=6, published=PUBLISHED,
                  rehearse={"hidden_size": 64, "ffn_dim": 96,
                            "num_hidden_layers": 3, "num_attention_heads": 2,
                            "max_position_embeddings": 256,
                            "vocab_size": 512, "micro_per_chip": 2})
    cell, conf = "train-reluffn-6l", "reluffn-125m-6l"
    (bench / "families" / "reluffn.py").write_text(NEW_FAMILY)
    (bench / "configs" / (conf + ".json")).write_text(json.dumps(config))
    (bench / "traffic" / "steps-seq512-micro16.json").write_text(json.dumps(
        {"kind": "steps", "seq": 512, "micro_per_chip": 16,
         "rehearse": {"seq": 128, "micro_per_chip": 1}}))
    counted = {"name": "train.steps_counted", "unit": "count",
               "better": "higher", "source": "program_counter",
               "layer": "trainer", "moves": "train_tokens_per_s_chip",
               "workloads": [cell]}
    dispatch = dict(counted, name="train.host_to_dispatch_ms_p90.reluffn",
                    unit="ms", better="lower")
    (bench / "metrics" / (counted["name"] + ".json")).write_text(json.dumps(
        dict(counted, reader="steps_counted",
             args={"series": "train_step_ms"})))
    (bench / "metrics" / (dispatch["name"] + ".json")).write_text(json.dumps(
        dict(dispatch, reader="registry_percentile",
             args={"histogram": "train/host_to_dispatch_ms", "q": 90})))
    (bench / "readers" / "counted.py").write_text(NEW_READER)

    manifest["configs"].append(
        {"name": conf, "source": source,
         "file": f"benchmarks/chip/configs/{conf}.json",
         "reduced": ["num_hidden_layers"], "why": "a test's configuration"})
    manifest["workloads"].append(
        {"name": cell, "config": conf, "traffic": "steps-seq512-micro16",
         "chips": 1, "why": "a test"})
    manifest["end_to_end"][0]["workloads"].append(cell)
    manifest["per_layer"] += [counted, dispatch]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    path = os.pathsep.join([str(tmp_path), PYTHONPATH])
    proc = _run(str(bench / "run.py"), "--workload", cell,
                "--seed", "5", "--seconds", "1", "--trace", "1",
                "--rehearse", cwd=tmp_path, extra_env={"PYTHONPATH": path})
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] > 0
    assert f"cell {cell}:" in proc.stdout
    # the new reader and the registry's reader found something to read
    said = next(ln for ln in proc.stdout.splitlines()
                if "readers gave a value for" in ln)
    assert repr(counted["name"]) in said and repr(dispatch["name"]) in said

    # the benchmark's own tests hold the addition, unedited
    held = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "tests/chip_bench/test_manifest.py",
         "tests/chip_bench/test_program_readers.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": path})
    assert held.returncode == 0, held.stdout[-4000:] + held.stderr[-2000:]
    assert " passed" in held.stdout and "failed" not in held.stdout

    after = _files(bench, tests)
    assert {p: b for p, b in after.items() if p in before} == before
    assert len(after) == len(before) + 6
