"""Fleet configuration (the ``serving.fleet`` sub-block).

Stdlib-only (same contract as ``serving/config.py``): ``runtime/
config.py`` reaches this dataclass through ``ServingConfig``, and that
import path must stay jax-free for the dependency-free tooling jobs
(ds_tpu_lint in CI).

Reference frame: DeepSpeed-Inference's multi-GPU serving architecture
(arXiv:2207.00032) — the layer ABOVE one engine: N supervised replicas,
a prefix-affinity front-end router, and (optionally) disaggregated
prefill/decode where prompt-heavy replicas hand page-granular KV to
decode replicas so bursty prompt traffic cannot starve steady-state
decode (docs/serving.md "Multi-replica fleet").
"""

from dataclasses import dataclass, field
from typing import Optional

from .supervision import SupervisionConfig

ROUTERS = ("prefix_affinity", "least_loaded")
BACKENDS = ("inprocess", "process")


@dataclass
class FleetConfig:
    """Multi-replica serving knobs.

    Every replica runs the SAME ``ServingConfig`` (minus this block):
    identical compiled shapes, identical page geometry — which is what
    makes the page handoff a transfer instead of a recompute, and what
    keeps routing decisions replayable (the router only ever reads
    deterministic per-replica state on the fleet step clock).
    """
    enabled: bool = True
    replicas: int = 2                # engines at fleet start
    backend: str = "inprocess"       # "inprocess" = N engines, one
                                     # process, lockstep clock (the
                                     # deterministic/CI path);
                                     # "process" = one worker subprocess
                                     # per replica (fleet/worker.py line
                                     # protocol + /healthz endpoint)
    router: str = "prefix_affinity"  # dispatch policy: route to the
                                     # replica whose radix prefix cache
                                     # most likely holds the prompt head,
                                     # least-loaded fallback; or pure
                                     # "least_loaded"
    affinity_queue_factor: float = 2.0
                                     # affinity yields to least-loaded
                                     # when the affine replica's queue
                                     # exceeds factor * slot_cap (a hot
                                     # prefix must not melt one replica)
    affinity_index_size: int = 512   # prompt-head runs remembered per
                                     # replica (LRU) by the router
    disaggregate: bool = False       # split roles: prefill replicas run
                                     # chunked prefill + first token then
                                     # hand page-granular KV to decode
                                     # replicas
    prefill_replicas: int = 1        # leading replicas that take the
                                     # prefill role when disaggregated
    health_every_steps: int = 8      # fleet steps between health sweeps
    max_missed_health: int = 2       # consecutive missed checks before a
                                     # replica is declared dead and its
                                     # in-flight requests requeue through
                                     # the router
    autoscale: bool = False          # act on ServingAutoscaler
                                     # target_replicas: spawn on
                                     # sustained backlog, drain via the
                                     # preemption/slot-cap path on
                                     # scale-down
    min_replicas: int = 1
    max_replicas: int = 8
    autoscale_every_steps: int = 16  # fleet steps between autoscaler
                                     # observations
    replica_telemetry: bool = False  # per-replica /metrics endpoints on
                                     # ephemeral ports (the router-level
                                     # endpoint is separate — see
                                     # ServingFleet.start_telemetry)
    aggregate_telemetry: bool = True # fleet telemetry aggregator
                                     # (observability/fleet.py): poll
                                     # every replica (scrape or direct
                                     # snapshot) on the cadence below and
                                     # serve the merged view from the
                                     # router's /metrics + /statusz
    aggregate_every_steps: int = 8   # fleet steps between aggregator
                                     # polls (bounded cadence — never per
                                     # engine step)
    stale_after_s: float = 30.0      # a replica whose last successful
                                     # sample is older than this reads
                                     # ``stale`` in the aggregated view
                                     # (dead vs one dropped scrape)
    replica_trace: bool = False      # process workers activate a span
                                     # tracer so their dumps can be
                                     # stitched into one fleet Chrome
                                     # trace (stitched_trace()); the
                                     # in-process backend records into
                                     # the router's own tracer
    flight_recorder_events: int = 256
                                     # fleet-level request-lifecycle ring
                                     # (submit/admit/handoff/failover/
                                     # finish on the fleet step clock);
                                     # 0 disables
    worker_reply_timeout_s: float = 120.0
                                     # process backend: how long the
                                     # manager waits on one worker reply
                                     # before declaring the pipe wedged
                                     # (WorkerProtocolError -> death ->
                                     # supervision)
    supervision: Optional[SupervisionConfig] = field(default=None)
                                     # self-healing policy (restart with
                                     # backoff, crash-loop retirement,
                                     # degraded disaggregation, handoff
                                     # retry budget); absent = defaults
                                     # (ENABLED — supervision.enabled:
                                     # false restores fatal/no-respawn
                                     # PR-12 semantics)
    federation: Optional["FederationConfig"] = field(default=None)
                                     # cross-host federation (socket
                                     # transport for remote non-child
                                     # replicas, HTTP front-end, rolling
                                     # update policy); absent/None =
                                     # single-host fleet, no peers — the
                                     # manager still reads rolling
                                     # defaults from None safely
    slo: Optional["SloConfig"] = field(default=None)
                                     # declarative SLO watch over the
                                     # aggregated telemetry sample
                                     # (observability/slo.py): fire/
                                     # clear hysteresis, bounded
                                     # incident log; absent = defaults
                                     # (watch DISABLED — slo.enabled:
                                     # true arms it)

    def __post_init__(self):
        # nested-dict lift, same contract as ServingConfig.__post_init__
        # ({"serving": {"fleet": {"supervision": {...}}}} arrives as a
        # plain dict); None means "all defaults", which keeps the
        # manager's config reads unconditional
        if self.supervision is None:
            self.supervision = SupervisionConfig()
        elif isinstance(self.supervision, dict):
            self.supervision = SupervisionConfig(**self.supervision)
        if isinstance(self.federation, dict):
            from .federation.config import FederationConfig
            self.federation = FederationConfig(**self.federation)
        if self.slo is None:
            from deepspeed_tpu.observability.slo import SloConfig
            self.slo = SloConfig()
        elif isinstance(self.slo, dict):
            from deepspeed_tpu.observability.slo import SloConfig
            self.slo = SloConfig(**self.slo)

    def validate(self) -> "FleetConfig":
        if self.replicas < 1:
            raise ValueError(
                f"serving.fleet.replicas must be >= 1, got {self.replicas}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"serving.fleet.backend must be one of {BACKENDS}, got "
                f"{self.backend!r}")
        if self.router not in ROUTERS:
            raise ValueError(
                f"serving.fleet.router must be one of {ROUTERS}, got "
                f"{self.router!r}")
        if self.affinity_queue_factor <= 0:
            raise ValueError(
                "serving.fleet.affinity_queue_factor must be > 0, got "
                f"{self.affinity_queue_factor}")
        if self.affinity_index_size < 1:
            raise ValueError(
                "serving.fleet.affinity_index_size must be >= 1, got "
                f"{self.affinity_index_size}")
        if self.disaggregate:
            if self.replicas < 2:
                raise ValueError(
                    "serving.fleet.disaggregate needs >= 2 replicas "
                    "(at least one prefill and one decode), got "
                    f"{self.replicas}")
            if not 1 <= self.prefill_replicas < self.replicas:
                raise ValueError(
                    f"serving.fleet.prefill_replicas must satisfy 1 <= n "
                    f"< replicas ({self.replicas}), got "
                    f"{self.prefill_replicas}")
        if self.health_every_steps < 1:
            raise ValueError(
                "serving.fleet.health_every_steps must be >= 1, got "
                f"{self.health_every_steps}")
        if self.max_missed_health < 1:
            raise ValueError(
                "serving.fleet.max_missed_health must be >= 1, got "
                f"{self.max_missed_health}")
        if self.min_replicas < 1 or self.max_replicas < self.min_replicas:
            raise ValueError(
                "serving.fleet needs 1 <= min_replicas <= max_replicas, "
                f"got min={self.min_replicas} max={self.max_replicas}")
        if self.autoscale_every_steps < 1:
            raise ValueError(
                "serving.fleet.autoscale_every_steps must be >= 1, got "
                f"{self.autoscale_every_steps}")
        if self.aggregate_every_steps < 1:
            raise ValueError(
                "serving.fleet.aggregate_every_steps must be >= 1, got "
                f"{self.aggregate_every_steps}")
        if self.stale_after_s <= 0:
            raise ValueError(
                "serving.fleet.stale_after_s must be > 0, got "
                f"{self.stale_after_s}")
        if self.flight_recorder_events < 0:
            raise ValueError(
                "serving.fleet.flight_recorder_events must be >= 0 "
                f"(0 disables), got {self.flight_recorder_events}")
        if self.worker_reply_timeout_s <= 0:
            raise ValueError(
                "serving.fleet.worker_reply_timeout_s must be > 0, got "
                f"{self.worker_reply_timeout_s}")
        self.supervision.validate()
        self.slo.validate()
        if self.federation is not None:
            self.federation.validate()
            if len(self.federation.peers) > self.replicas:
                raise ValueError(
                    "serving.fleet.federation.peers lists "
                    f"{len(self.federation.peers)} peers but the fleet "
                    f"only has {self.replicas} replicas — peers fill the "
                    "leading replica ids")
        if self.disaggregate and self.min_replicas < 2:
            # a disaggregated fleet can never drain below one prefill +
            # one decode replica
            self.min_replicas = 2
        return self

    def role_for(self, replica_id: int) -> str:
        """Role of replica ``replica_id`` at spawn: the leading
        ``prefill_replicas`` take the prefill role when disaggregated,
        everything else serves end-to-end ("full") or decode-only."""
        if not self.disaggregate:
            return "full"
        return ("prefill" if replica_id < self.prefill_replicas
                else "decode")
