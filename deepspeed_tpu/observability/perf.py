"""Performance accounting: step-time percentiles, tokens/sec, MFU.

MFU (model FLOPs utilization, the PaLM/MLPerf-TPU convention used by
the TPU-v3 scaling study in PAPERS.md) = achieved model FLOPs per
second / peak chip FLOPs. Achieved FLOPs come from the STATIC per-model
estimator (profiling/flops_profiler.transformer_flops_per_token) — the
model's algorithmic work, not whatever XLA actually executed, so remat
recompute never inflates the number. Peak FLOPs come from the small
chip table below; override with ``observability.peak_tflops`` (or
``chip``) for hardware the table doesn't know.

Step times are host wall-clock deltas between step ends (the engine's
per-step effects barrier keeps the host clock honest) in a sliding
window; the bounded-cadence ``DeviceProbe`` supplies occasional
device-accurate drains without per-step syncs.
"""

import time
from collections import deque
from typing import Optional

# Peak dense bf16 FLOPs per CHIP (not per core / per host), in TFLOP/s.
# Sources: published TPU/ GPU spec sheets; serving and training use the
# same number (we account bf16 matmul peak everywhere).
CHIP_PEAK_TFLOPS = {
    "tpu-v2": 45.0,
    "tpu-v3": 123.0,
    "tpu-v4": 275.0,
    "tpu-v5e": 197.0,
    "tpu-v5p": 459.0,
    "tpu-v6e": 918.0,
    "a100": 312.0,
    "h100": 989.0,
}

# device_kind strings as reported by jax -> chip-table keys
_DEVICE_KIND_ALIASES = {
    "tpu v2": "tpu-v2",
    "tpu v3": "tpu-v3",
    "tpu v4": "tpu-v4",
    "tpu v5 lite": "tpu-v5e",
    "tpu v5e": "tpu-v5e",
    "tpu v5p": "tpu-v5p",
    "tpu v6 lite": "tpu-v6e",
    "tpu v6e": "tpu-v6e",
}


def detect_chip() -> Optional[str]:
    """Chip-table key for the local accelerator. None off-accelerator
    (the CPU backend has no peak: MFU is "not measured" there). On
    platform ``tpu`` a device kind the table does not know raises — a
    silently absent MFU reads as a healthy run with nothing to report."""
    import jax
    dev = jax.local_devices()[0]
    kind = dev.device_kind.lower()
    if kind in _DEVICE_KIND_ALIASES:
        return _DEVICE_KIND_ALIASES[kind]
    key = kind.replace(" ", "-")
    if key in CHIP_PEAK_TFLOPS:
        return key
    if dev.platform == "tpu":
        raise ValueError(
            f"unknown TPU device kind {dev.device_kind!r} for MFU "
            f"accounting — known: {sorted(_DEVICE_KIND_ALIASES)}; add it "
            "to observability/perf.py or set observability.peak_tflops")
    return None


def resolve_peak_flops(config) -> Optional[float]:
    """Per-chip peak FLOP/s for MFU from an ObservabilityConfig:
    ``peak_tflops`` override wins, else ``chip`` (or the detected device
    kind) looked up in the table. None = MFU not measured (the CPU
    backend without an override)."""
    if getattr(config, "peak_tflops", None):
        return float(config.peak_tflops) * 1e12
    chip = getattr(config, "chip", None) or detect_chip()
    if chip is None:
        return None
    key = chip.lower()
    if key not in CHIP_PEAK_TFLOPS:
        raise ValueError(
            f"unknown chip {chip!r} for MFU accounting — known: "
            f"{sorted(CHIP_PEAK_TFLOPS)}; or set observability.peak_tflops")
    return CHIP_PEAK_TFLOPS[key] * 1e12


class PerfAccountant:
    """Sliding-window step-time stats + tokens/sec + MFU.

    ``on_step(tokens)`` marks one optimizer step's end; deltas between
    consecutive ends (after ``warmup`` steps — the first covers
    compilation) feed the window. ``flops_per_step`` is set once by the
    owner (engine resolves it lazily from the static estimator) and
    turns the window into achieved-TFLOPs/MFU."""

    def __init__(self, window: int = 256, warmup: int = 2,
                 peak_flops: Optional[float] = None):
        self.step_ms = deque(maxlen=max(2, int(window)))
        self.warmup = int(warmup)
        self.peak_flops = peak_flops
        self.flops_per_step: Optional[float] = None
        self.tokens_per_step: Optional[int] = None
        self._seen = 0
        self._last_end = None

    def on_step(self, tokens: Optional[int] = None):
        now = time.perf_counter()
        self._seen += 1
        if tokens:
            # host int by contract (batch-shape metadata, never a device
            # scalar — an int() here would read as a TS002 sync)
            self.tokens_per_step = tokens
        if self._last_end is not None and self._seen > self.warmup:
            self.step_ms.append((now - self._last_end) * 1e3)
        self._last_end = now

    def summary(self) -> dict:
        """Host-float stats dict; empty until the window has samples.
        Keys: step_time_{mean,p50,p95}_ms, steps_measured, and (when
        tokens/flops are known) tokens_per_sec / achieved_tflops / mfu."""
        if not self.step_ms:
            return {}
        from .metrics import percentile
        s = sorted(self.step_ms)
        n = len(s)
        mean_ms = sum(s) / n
        out = {
            "step_time_mean_ms": mean_ms,
            "step_time_p50_ms": percentile(s, 50),
            "step_time_p95_ms": percentile(s, 95),
            "steps_measured": n,
        }
        mean_s = mean_ms / 1e3
        if self.tokens_per_step:
            out["tokens_per_sec"] = self.tokens_per_step / mean_s
        if self.flops_per_step:
            achieved = self.flops_per_step / mean_s
            out["achieved_tflops"] = achieved / 1e12
            if self.peak_flops:
                out["mfu"] = achieved / self.peak_flops
        return out
