"""Serving engine configuration (the ``serving`` config block).

Stdlib-only on purpose: ``runtime/config.py`` imports this dataclass to
wire the block into ``DeepSpeedConfig``, and that module must stay
importable without jax (the ds_tpu_lint job runs dependency-free).
"""

from dataclasses import dataclass, field
from typing import Optional

from .fleet.config import FleetConfig
from .paging.config import PagingConfig
from .qos import QosConfig


@dataclass
class QuantizeConfig:
    """Serving quantization knobs (the ``serving.quantize`` sub-block).

    ``weights``: "int8" stores every big matmul weight int8 with
    per-output-channel scales at engine build (module_inject/
    module_quantize.py) — the decode matmuls consume them through the
    fused-dequant Pallas kernel, so HBM holds and streams HALF the
    weight bytes (reference analog: the *_int8 inference gemms).

    ``kv``: "int8" stores the paged KV pool int8 with per-page scale
    planes (quantize on scatter, dequantize inside the paged-attention
    kernel's page loop / on gather) — halving page bytes doubles pool
    density again on top of paging.

    Parity ladder (docs/serving.md): weights-only int8 is token-exact
    vs a generate() reference over the SAME int8 params under greedy
    sampling; int8 KV rides the bounded-error rung (logit max-abs-err
    + downstream-token agreement, asserted in
    tests/unit/test_quantized_serving.py).
    """
    weights: Optional[str] = None    # None | "int8"
    kv: Optional[str] = None         # None | "int8"
    min_size: int = 4096             # smallest weight (elements) to
                                     # quantize; everything below stays
                                     # in its own dtype

    def validate(self):
        for field_name, val in (("weights", self.weights), ("kv", self.kv)):
            if val not in (None, "int8"):
                raise ValueError(
                    f"serving.quantize.{field_name} must be null or "
                    f"'int8', got {val!r}")
        if self.min_size < 1:
            raise ValueError(
                f"serving.quantize.min_size must be >= 1, got "
                f"{self.min_size}")
        return self


@dataclass
class SpeculationConfig:
    """Token-exact self-speculative decoding (the ``serving.speculation``
    sub-block, docs/serving.md "Speculative decoding").

    Draft-free prompt-lookup speculation on the deterministic step
    clock: a host-side n-gram proposer (serving/speculation.py) matches
    the tail of each slot's ``prompt + generated`` sequence against its
    own history and proposes up to ``max_spec_tokens`` continuation
    tokens per iteration; ONE batched verification program checks all
    proposals in a single multi-token decode step and accepts the
    longest prefix agreeing with greedy argmax — so accepted iterations
    emit k+1 tokens for roughly the cost of one decode dispatch, and
    the output stays bitwise identical to the non-speculative engine.

    Greedy-only by construction: the acceptance rule IS greedy argmax,
    so ``validate`` refuses the block on a sampling engine
    (temperature > 0) rather than silently changing the distribution.
    """
    enabled: bool = True
    max_spec_tokens: int = 4         # k: proposal budget per slot per
                                     # iteration (the QoS ladder sheds
                                     # this to 0 under pressure — before
                                     # any request sheds)
    ngram_max: int = 3               # longest tail n-gram the proposer
                                     # tries to match (longest first)
    ngram_min: int = 1               # shortest n-gram worth matching

    def validate(self, temperature: float) -> "SpeculationConfig":
        if self.max_spec_tokens < 1:
            raise ValueError(
                f"serving.speculation.max_spec_tokens must be >= 1, got "
                f"{self.max_spec_tokens}")
        if self.ngram_min < 1:
            raise ValueError(
                f"serving.speculation.ngram_min must be >= 1, got "
                f"{self.ngram_min}")
        if self.ngram_max < self.ngram_min:
            raise ValueError(
                f"serving.speculation.ngram_max ({self.ngram_max}) must "
                f"be >= ngram_min ({self.ngram_min})")
        if self.enabled and temperature != 0.0:
            raise ValueError(
                "serving.speculation requires greedy sampling "
                f"(temperature=0.0, got {temperature}): the acceptance "
                "rule is greedy argmax, and speculating under a sampling "
                "engine would silently change the output distribution")
        return self


@dataclass
class ServingConfig:
    """Continuous-batching serving knobs (reference analog: the
    init_inference kwargs + DeepSpeed-MII deployment config).

    The engine decodes a fixed batch of ``num_slots`` rows of at most
    ``max_len`` tokens each, their K/V held in a block-paged pool (the
    ``paging`` sub-block); prompts prefill in page-aligned chunks, so XLA
    compiles one prefill executable per chunk width and ONE decode
    executable total.
    """
    num_slots: int = 8
    max_len: int = 1024              # per-request token budget (prompt+output)
    max_queue: Optional[int] = None  # submit() raises past this depth
    eos_token_id: Optional[int] = None
    default_max_new_tokens: int = 128
    temperature: float = 0.0         # engine-wide sampling (greedy default)
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    pipeline_depth: int = 1          # decode dispatches in flight before the
                                     # host reads tokens back (1 overlaps the
                                     # device step with host scheduling)
    default_deadline_steps: Optional[int] = None
                                     # queue TTL (engine iterations) applied
                                     # to requests that don't set their own
                                     # deadline_steps; None = wait forever
    metrics_interval: int = 50       # engine iterations between monitor
                                     # flushes (never per-step host syncs)
    flight_recorder_events: int = 256
                                     # bounded request-lifecycle ring
                                     # (observability/fleet.py): the
                                     # last-N-requests timeline the
                                     # partial-snapshot/crash path dumps;
                                     # 0 disables recording
    seed: int = 0
    paging: PagingConfig = field(default_factory=PagingConfig)
                                     # the block-paged KV pool (serving/
                                     # paging/): page size, pool size, prefix
                                     # cache, prefill chunking, decode kernel
    qos: Optional[QosConfig] = None  # priority classes / SLO shedding /
                                     # degradation ladder / watchdog
                                     # (serving/qos.py, docs/serving.md):
                                     # absent or enabled=False keeps the
                                     # pre-QoS FIFO engine untouched
    quantize: Optional[QuantizeConfig] = None
                                     # int8 weight-only serving + int8 KV
                                     # pages (docs/serving.md "Quantized
                                     # serving"); absent = full-precision
    fleet: Optional[FleetConfig] = None
                                     # multi-replica fleet (serving/fleet/,
                                     # docs/serving.md "Multi-replica
                                     # fleet"): replica manager + prefix-
                                     # affinity router + disaggregated
                                     # prefill/decode; absent = one engine
    speculation: Optional[SpeculationConfig] = None
                                     # token-exact self-speculative decode
                                     # (serving/speculation.py, docs/
                                     # serving.md "Speculative decoding");
                                     # absent or enabled=False keeps the
                                     # one-token-per-step decode loop
                                     # untouched

    def __post_init__(self):
        # nested-block plumbing: runtime/config.py's dict_to_dataclass is
        # shallow, so {"serving": {"paging": {...}}} arrives here as a dict
        if self.paging is None:             # a JSON null: the block is absent
            self.paging = PagingConfig()
        elif isinstance(self.paging, dict):
            self.paging = PagingConfig(**self.paging)
        if isinstance(self.qos, dict):
            self.qos = QosConfig(**self.qos)
        if isinstance(self.quantize, dict):
            self.quantize = QuantizeConfig(**self.quantize)
        if isinstance(self.fleet, dict):
            self.fleet = FleetConfig(**self.fleet)
        if isinstance(self.speculation, dict):
            self.speculation = SpeculationConfig(**self.speculation)

    def validate(self):
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {self.max_len}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 (or null for unbounded), got "
                f"{self.max_queue}")
        if self.default_max_new_tokens < 1:
            raise ValueError("default_max_new_tokens must be >= 1, got "
                             f"{self.default_max_new_tokens}")
        if self.pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {self.pipeline_depth}")
        if (self.default_deadline_steps is not None
                and self.default_deadline_steps < 1):
            raise ValueError(
                f"default_deadline_steps must be >= 1 (or null), got "
                f"{self.default_deadline_steps}")
        if self.metrics_interval < 1:
            raise ValueError(
                f"metrics_interval must be >= 1, got {self.metrics_interval}")
        if self.flight_recorder_events < 0:
            raise ValueError(
                f"flight_recorder_events must be >= 0 (0 disables), got "
                f"{self.flight_recorder_events}")
        self.paging.validate(self.cache_len)
        if self.qos is not None:
            self.qos.validate()
        if self.quantize is not None:
            self.quantize.validate()
        if self.fleet is not None:
            self.fleet.validate()
        if self.speculation is not None:
            self.speculation.validate(self.temperature)
        return self

    @property
    def weights_int8(self) -> bool:
        """True when serving should int8-quantize weights at build."""
        return self.quantize is not None and self.quantize.weights == "int8"

    @property
    def kv_int8(self) -> bool:
        """True when the paged KV pool stores int8 pages."""
        return self.quantize is not None and self.quantize.kv == "int8"

    @property
    def qos_enabled(self) -> bool:
        """True when the QoS layer is configured AND enabled."""
        return self.qos is not None and self.qos.enabled

    @property
    def fleet_enabled(self) -> bool:
        """True when the multi-replica fleet is configured AND enabled."""
        return self.fleet is not None and self.fleet.enabled

    @property
    def spec_enabled(self) -> bool:
        """True when self-speculative decoding is configured AND enabled."""
        return self.speculation is not None and self.speculation.enabled

    @property
    def cache_len(self) -> int:
        """Slot capacity rounded up to a 128 multiple so the Pallas decode
        kernel's tiling always applies (generation.py convention).

        With speculation enabled the capacity also covers
        ``max_spec_tokens`` of write headroom past ``max_len``: the
        verification step writes k+1 candidate tokens at each slot's
        frontier BEFORE acceptance decides how many are real, and the
        headroom guarantees those writes never clamp backwards into a
        live slot's valid prefix (an active slot holds at most
        ``max_len - 2`` tokens, so ``max_len + k`` positions always fit
        the k+1-token window)."""
        pad = self.speculation.max_spec_tokens if self.spec_enabled else 0
        return (self.max_len + pad + 127) // 128 * 128
