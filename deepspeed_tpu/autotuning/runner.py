"""Autotuning launch layer: crash-isolated candidates + the CLI entry.

Reference: the `deepspeed --autotuning {tune,run}` path —
launcher/runner.py:304 hands off to autotuning/autotuner.py, whose
ResourceManager (autotuning/scheduler.py:27) launches every experiment
as its own process and reads metrics back from files.

Why subprocess isolation matters: an in-process candidate that OOMs at
compile time can wedge the accelerator client and pollutes the surviving
process's HBM high-water mark. A candidate process that dies takes its
client with it; the tuner just records the point as infeasible. A chip
belongs to one process at a time, so the tuner process itself stays off
JAX for as long as candidates run.

Candidate contract (reference: experiments receive their exp config via
--deepspeed_config): the user script is launched as

    python <script> <user args...>

with ``DS_TPU_AUTOTUNING_CANDIDATE=<path to candidate config json>`` in
the environment. The script builds its engine from that config, runs a
few steps, and reports by printing one line:

    AUTOTUNE_RESULT: {"samples_per_sec": <float>, "step_ms": <float>}

(`report_result` below prints it). Crash, timeout or a missing result
line = infeasible point.
"""

import json
import os
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Optional

from ..utils.logging import logger

RESULT_PREFIX = "AUTOTUNE_RESULT: "


def report_result(samples_per_sec: float, step_ms: Optional[float] = None):
    """Call from the candidate script after measuring (see module doc)."""
    print(RESULT_PREFIX + json.dumps(
        {"samples_per_sec": float(samples_per_sec),
         "step_ms": None if step_ms is None else float(step_ms)}),
        flush=True)


def candidate_config() -> Optional[Dict[str, Any]]:
    """The candidate's config dict when running under the tuner, else
    None (so one script serves both tuning and real training)."""
    path = os.environ.get("DS_TPU_AUTOTUNING_CANDIDATE")
    if not path:
        return None
    with open(path) as f:
        return json.load(f)


class SubprocessMeasurer:
    """measure(config) -> metrics dict or raises — each candidate in its
    own process (the reference scheduler's per-experiment launch)."""

    def __init__(self, script: str, script_args: Optional[List[str]] = None,
                 timeout_s: float = 600.0, env: Optional[Dict] = None):
        self.script = script
        self.script_args = list(script_args or [])
        self.timeout_s = timeout_s
        self.env = env

    def __call__(self, config: Dict[str, Any]) -> Dict[str, Any]:
        with tempfile.NamedTemporaryFile(
                "w", suffix=".json", delete=False) as f:
            json.dump(config, f)
            cfg_path = f.name
        env = dict(self.env if self.env is not None else os.environ)
        env["DS_TPU_AUTOTUNING_CANDIDATE"] = cfg_path
        from ..utils.host_env import assert_not_holding_chip
        assert_not_holding_chip("the autotuner")
        try:
            proc = subprocess.run(
                [sys.executable, self.script] + self.script_args,
                env=env, capture_output=True, text=True,
                timeout=self.timeout_s)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"candidate timed out after {self.timeout_s:.0f}s")
        finally:
            try:
                os.unlink(cfg_path)
            except OSError:
                pass
        if proc.returncode != 0:
            raise RuntimeError(
                f"candidate exited {proc.returncode}: "
                f"{proc.stderr.strip()[-500:]}")
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith(RESULT_PREFIX):
                return json.loads(line[len(RESULT_PREFIX):])
        raise RuntimeError("candidate produced no AUTOTUNE_RESULT line; "
                           f"stdout tail: {proc.stdout.strip()[-300:]}")


def run_autotuning_cli(args) -> int:
    """`ds_tpu --autotuning tune script.py --autotuning_config at.json`
    (reference: runner.py:304). The at.json schema:

    {
      "micro_batches": [1, 2, 4, 8],
      "zero_stages": [0, 1, 2, 3],
      "gas_values": [1, 8],                 # optional
      "base_config": { ... ds config ... } | "path/to/ds_config.json",
      "dp_world_size": 1 | "auto",          # "auto" probes jax.devices()
                                            # in a subprocess (the parent
                                            # never touches the backend)
      "tuner_type": "model_based",          # optional
      "early_stop": null,                   # optional
      "timeout_s": 600,                     # optional, per candidate
      "results_dir": "autotuning_results",  # optional
      "model_info": {                       # optional: memory pre-pass
        "num_params": 125000000,            # (reference model_info block)
        "hidden_size": 768, "num_layers": 12, "seq_len": 1024
      },
      "memory_budget_bytes": 16e9           # optional, with model_info
    }
    """
    from .autotuner import Autotuner
    with open(args.autotuning_config) as f:
        at = json.load(f)
    base = at["base_config"]
    if isinstance(base, str):
        with open(base) as f:
            base = json.load(f)

    dp = at.get("dp_world_size", 1)
    if dp == "auto":
        # probe the device count in a SUBPROCESS: initialising jax here
        # would make this process hold the chip, and every candidate
        # after it would fail or hang reaching the device
        why = None
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(len(jax.devices()))"],
                capture_output=True, text=True, timeout=240)
            if r.returncode == 0:
                dp = int(r.stdout.strip().splitlines()[-1])
            else:
                dp, why = 1, f"probe exited {r.returncode}: " \
                    f"{r.stderr.strip()[-200:]}"
        except subprocess.TimeoutExpired:
            dp, why = 1, "probe timed out after 240s (accelerator " \
                "runtime wedged?)"
        except (ValueError, IndexError):
            dp, why = 1, f"unparseable probe output: {r.stdout[-100:]!r}"
        if why:
            # dp=1 on a multi-chip rig makes EVERY candidate infeasible —
            # make the cause loud, not an info line
            logger.warning(
                f"dp_world_size=auto fell back to 1 ({why}); on a "
                "multi-chip host set dp_world_size explicitly or every "
                "candidate will fail the batch-arithmetic check")
        else:
            logger.info(f"autotuning dp_world_size=auto resolved to {dp}")

    tuner = Autotuner(
        make_engine=None, make_batch=None,
        measurer=SubprocessMeasurer(
            args.user_script, args.user_args,
            timeout_s=float(at.get("timeout_s", 600.0))),
        results_dir=at.get("results_dir", "autotuning_results"))
    space_kw = dict(
        zero_stages=at.get("zero_stages", [0, 1, 2, 3]),
        micro_batches=at.get("micro_batches", [1, 2, 4, 8]),
        dp_world_size=int(dp),
        gas_values=at.get("gas_values"))
    best = tuner.tune(
        base, tuner_type=at.get("tuner_type", "model_based"),
        early_stop=at.get("early_stop"),
        model_info=at.get("model_info"),
        memory_budget_bytes=at.get("memory_budget_bytes"),
        **space_kw)
    print(json.dumps({"best_config": best.config,
                      "samples_per_sec": best.samples_per_sec,
                      "step_ms": best.step_ms}, indent=2, default=str))
    # reference prints the experiment table at the end of tune()
    for i, res in enumerate(tuner.results):
        z = (res.config.get("zero_optimization") or {}).get("stage")
        mb = res.config.get("train_micro_batch_size_per_gpu")
        gas = res.config.get("gradient_accumulation_steps", 1)
        metric = (f"{res.samples_per_sec:10.1f}" if res.feasible
                  else "infeasible")
        logger.info(f"exp {i:3d}: stage={z} micro={mb} gas={gas} "
                    f"samples/s={metric}"
                    + (f" ({res.error.strip()})" if res.error else ""))
    return 0
