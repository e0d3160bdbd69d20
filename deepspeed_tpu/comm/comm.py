"""Communication facade.

TPU-native analog of ``deepspeed.comm`` (reference: deepspeed/comm/comm.py).
The reference wraps torch.distributed (NCCL); here the same op vocabulary is
backed by two paths:

1. **In-jit path** — the hot path. Functions take ``group`` as a mesh-axis
   name (or tuple of names) and lower to ``jax.lax`` collectives
   (psum / all_gather / psum_scatter / all_to_all / ppermute) that XLA
   schedules over ICI/DCN. These must be called inside ``shard_map``/``jit``
   with the relevant axes bound — exactly where the reference called NCCL
   from CUDA streams.

2. **Host path** — for benchmarks and eager-mode tests: ``*_host`` variants
   wrap the op in a one-shot ``shard_map`` over the global mesh.

``init_distributed`` (reference: comm/comm.py:577) performs the multi-host
rendezvous via ``jax.distributed.initialize`` over DCN instead of a
NCCL/MPI bootstrap.
"""

import os
import time
from enum import Enum
from typing import Optional

from ..observability.metrics import get_registry, record_traced_collective
from ..utils.logging import logger, log_dist
from .mesh import (MESH_AXES, MeshSpec, build_mesh, get_global_mesh,
                   peek_global_mesh, set_global_mesh,
                   axis_size, dp_world_size, mp_world_size, pp_world_size)


class ReduceOp(Enum):
    SUM = 0
    PRODUCT = 1
    MIN = 2
    MAX = 3
    AVG = 4
    UNUSED = 5


_INITIALIZED = False
_COMMS_LOGGER = None


def is_initialized() -> bool:
    return _INITIALIZED


def init_distributed(dist_backend: str = "ici",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Multi-host rendezvous (reference: deepspeed/comm/comm.py:577).

    Single-process (one host driving its local chips) needs no rendezvous.
    Multi-host reads coordinator info from args or env
    (``DS_COORDINATOR_ADDRESS``/``DS_NUM_PROCESSES``/``DS_PROCESS_ID``, or the
    standard JAX/cloud-TPU envs that jax.distributed auto-detects).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    import jax

    coordinator_address = coordinator_address or os.environ.get("DS_COORDINATOR_ADDRESS")
    num_processes = num_processes or _env_int("DS_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _env_int("DS_PROCESS_ID")

    if coordinator_address is not None:
        if verbose:
            # Plain logger: log_dist queries jax.process_index(), which would
            # initialize the local backend before the rendezvous below.
            logger.info(f"Initializing distributed runtime: coordinator={coordinator_address} "
                        f"nprocs={num_processes} pid={process_id}")
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    elif world_size > 1 or _env_int("DS_NUM_PROCESSES", 0) > 1:
        # Fall back to jax auto-detection (GKE / TPU-VM metadata).
        jax.distributed.initialize()
    _INITIALIZED = True
    if verbose:
        log_dist(
            f"Distributed backend ready: {jax.process_count()} process(es), "
            f"{jax.device_count()} global device(s), platform={jax.default_backend()}",
            ranks=[0])


def _env_int(name, default=None):
    v = os.environ.get(name)
    return int(v) if v is not None else default


# ---------------------------------------------------------------------------
# Rank / world info. In the reference a "rank" is one GPU process; here a
# process drives many chips, so rank==process index and world==device count.
# ---------------------------------------------------------------------------

def get_rank() -> int:
    import jax
    return jax.process_index()


def get_world_size(group=None) -> int:
    import jax
    if group is None:
        return jax.device_count()
    return axis_size(group)


def get_local_rank() -> int:
    """Rank within the host. One JAX process drives all of a host's chips, so
    this is 0 unless the launcher packs several processes per host (then it
    exports DS_LOCAL_RANK, as the reference launcher exported LOCAL_RANK)."""
    return int(os.environ.get("DS_LOCAL_RANK", 0))


def barrier(group=None, name="ds_barrier"):
    """Cross-host barrier: all processes sync via a named global-device sync
    (reference: comm.py barrier -> NCCL barrier). Also flushes any dispatched
    async device work on this host."""
    import jax
    jax.effects_barrier()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


# ---------------------------------------------------------------------------
# Collective accounting (docs/observability.md, "Collective accounting").
#
# In-jit collectives execute inside XLA programs — host-timing one would
# require a per-op sync (exactly what TS002 forbids). Instead every
# wrapper records AT TRACE TIME: op, axis, dtype, and payload bytes go
# into a trace span (``comm/<op>``, carried in the span args) and the
# process tally in observability/metrics.py (``comm/traced_bytes/...``
# counters, keyed op:axis so ICI-bound model/fsdp traffic separates from
# DCN-bound data traffic). TrackedProgram diffs the tally around a
# compiling dispatch, turning the static record into a per-program
# bytes-moved-per-call estimate and a cumulative executed-traffic
# counter. Achieved bytes/sec is measurable only where a wall clock is
# honest — the host-path ops below, via the comms logger + the
# ``comm/host_bytes_per_s`` histogram.
# ---------------------------------------------------------------------------

def _group_label(group) -> str:
    """Stable axis label for tally keys and span args ("all" = whole
    mesh; tuples join with '+')."""
    if group is None:
        return "all"
    if isinstance(group, str):
        return group
    return "+".join(str(g) for g in group)


def _payload_nbytes(tensor) -> int:
    """Payload bytes from STATIC shape/dtype metadata — works on traced
    values (aval shapes are python ints), never reads device data."""
    shape = getattr(tensor, "shape", None)
    dtype = getattr(tensor, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = dtype.itemsize
    except AttributeError:
        import numpy as np
        itemsize = np.dtype(dtype).itemsize
    return n * int(itemsize)


def _note_collective(op: str, group, tensor, nbytes: Optional[int] = None):
    """Record one collective's payload in the trace-time tally
    (``comm/traced_calls|traced_bytes/<op>:<axis>``). No span: under
    ``jit`` this runs while the program traces, once per compile, so a
    duration here would be Python tracing time under a collective's
    name."""
    if nbytes is None:
        nbytes = _payload_nbytes(tensor)
    record_traced_collective(op, _group_label(group), nbytes)


# ---------------------------------------------------------------------------
# In-jit collectives (call inside shard_map with the axis bound).
# ---------------------------------------------------------------------------

def _declared_axes():
    """Axis names a collective may legally bind: the MESH_AXES vocabulary
    plus whatever the current global/abstract mesh declares (covers user
    shard_maps over custom meshes)."""
    axes = set(MESH_AXES)
    mesh = peek_global_mesh()
    if mesh is not None:
        axes.update(mesh.axis_names)
    from jax.sharding import get_abstract_mesh
    am = get_abstract_mesh()
    if not am.empty:
        axes.update(am.axis_names)
    return axes


def _axis(group):
    """Resolve+validate a group argument. A typo'd axis fails HERE with
    the declared axes listed, not five frames deep inside lax
    (ds_tpu_lint SC001 is the static half of this check)."""
    if group is None:
        return MESH_AXES  # whole mesh
    names = (group,) if isinstance(group, str) else tuple(group)
    declared = _declared_axes()
    bad = [n for n in names if isinstance(n, str) and n not in declared]
    if bad:
        raise ValueError(
            f"unknown mesh axis/group {bad[0]!r}: declared axes are "
            f"{tuple(sorted(declared))}")
    return group


def all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group=None):
    """lax.psum/pmean/... over a mesh axis (reference: comm.py:500)."""
    import jax
    axis = _axis(group)
    if op not in (ReduceOp.SUM, ReduceOp.AVG, ReduceOp.MAX, ReduceOp.MIN,
                  ReduceOp.PRODUCT):
        # validate BEFORE recording: a rejected op must not inflate the
        # traced-bytes tally (or a compiling program's attribution)
        raise ValueError(f"Unsupported reduce op {op}")
    _note_collective("all_reduce", group, tensor)
    if op == ReduceOp.SUM:
        return jax.lax.psum(tensor, axis)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(tensor, axis)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(tensor, axis)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(tensor, axis)
    # PRODUCT: no lax product-reduce primitive — gather the factors
    # and multiply. (Correct for zeros/negatives, unlike
    # exp(psum(log)).)
    import jax.numpy as jnp
    gathered = jax.lax.all_gather(tensor, axis, axis=0, tiled=False)
    return jnp.prod(gathered, axis=0)


def inference_all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, group="model"):
    return all_reduce(tensor, op=op, group=group)


def all_gather(tensor, group=None, axis: int = 0, tiled: bool = True):
    """lax.all_gather over a mesh axis (reference: all_gather_base comm.py:304).

    ``tiled=True`` concatenates along ``axis`` (torch all_gather_base
    semantics); ``tiled=False`` stacks a new leading dim.
    """
    import jax
    _note_collective("all_gather", group, tensor)
    return jax.lax.all_gather(tensor, _axis(group), axis=axis,
                              tiled=tiled)


def reduce_scatter(tensor, op: ReduceOp = ReduceOp.SUM, group=None, scatter_dimension: int = 0):
    """lax.psum_scatter (reference: reduce_scatter_fn comm.py:256)."""
    import jax
    assert op in (ReduceOp.SUM, ReduceOp.AVG)
    _note_collective("reduce_scatter", group, tensor)
    out = jax.lax.psum_scatter(tensor, _axis(group),
                               scatter_dimension=scatter_dimension,
                               tiled=True)
    if op == ReduceOp.AVG:
        out = out / axis_size(_axis(group))
    return out


def all_to_all_single(tensor, group=None, split_axis: int = 0, concat_axis: int = 0):
    """lax.all_to_all (reference: all_to_all_single comm.py:355)."""
    import jax
    _note_collective("all_to_all", group, tensor)
    return jax.lax.all_to_all(tensor, _axis(group),
                              split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def broadcast(tensor, src: int = 0, group=None):
    """Broadcast from mesh-coordinate ``src`` along the group axis.

    Implemented as select+psum — inside SPMD all members compute; the
    src member's value wins (reference: comm.py broadcast).
    """
    import jax
    import jax.numpy as jnp
    axis = _axis(group)
    _note_collective("broadcast", group, tensor)
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == src, tensor, jnp.zeros_like(tensor))
    return jax.lax.psum(masked, axis)


def ppermute(tensor, perm, group):
    """Neighbor exchange (pipeline p2p / ring attention building block)."""
    import jax
    _note_collective("ppermute", group, tensor)
    return jax.lax.ppermute(tensor, _axis(group), perm)


def send_recv_next(tensor, group):
    """Rotate +1 along a ring: rank i's value goes to rank i+1 (wraps)."""
    n = axis_size(group)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return ppermute(tensor, perm, group)


def send_recv_prev(tensor, group):
    """Rotate -1 along a ring: rank i's value goes to rank i-1 (wraps)."""
    n = axis_size(group)
    perm = [(i, (i - 1) % n) for i in range(n)]
    return ppermute(tensor, perm, group)


def axis_index(group):
    import jax
    return jax.lax.axis_index(_axis(group))


# ---------------------------------------------------------------------------
# Host-level variants: one-shot shard_map over the global mesh. Used by the
# communication benchmarks (ds_bench analog) and eager tests.
# ---------------------------------------------------------------------------

def _host_collective(fn, tensor, group):
    import jax
    from jax.sharding import PartitionSpec as P
    from ..utils.jax_compat import shard_map

    mesh = get_global_mesh()
    axis = _axis(group)
    spec = P(axis)  # shard leading dim over the group
    f = shard_map(fn, mesh, (spec,), spec)
    return jax.jit(f)(tensor)


def all_reduce_host(tensor, op: ReduceOp = ReduceOp.SUM, group="data"):
    return _host_collective(lambda t: all_reduce(t, op=op, group=group), tensor, group)


def all_gather_host(tensor, group="data"):
    return _host_collective(lambda t: all_gather(t, group=group), tensor, group)


def reduce_scatter_host(tensor, group="data"):
    return _host_collective(lambda t: reduce_scatter(t, group=group), tensor, group)


def all_to_all_host(tensor, group="data"):
    return _host_collective(lambda t: all_to_all_single(t, group=group), tensor, group)


# ---------------------------------------------------------------------------
# Comms logging (reference: timed_op decorator comm.py:111 + CommsLogger).
# Host-path ops are wall-clock timed; in-jit ops are recorded at trace time.
# ---------------------------------------------------------------------------

class CommsLogger:
    def __init__(self, verbose=False, debug=False):
        self.verbose = verbose
        self.debug = debug
        self.comms_dict = {}

    def append(self, record_name, latency, msg_size):
        entry = self.comms_dict.setdefault(record_name, {})
        sz = entry.setdefault(msg_size, [0, 0.0])
        sz[0] += 1
        sz[1] += latency
        if self.verbose:
            logger.info(f"comm op: {record_name} | size: {msg_size} | latency(ms): {latency*1e3:.3f}")

    def log_all(self):
        from ..utils.logging import log_dist
        for name, sizes in self.comms_dict.items():
            for msg_size, (count, total) in sorted(sizes.items()):
                avg = total / max(count, 1)
                bw = msg_size / max(avg, 1e-12) / 1e9
                log_dist(f"{name}: size={msg_size}B count={count} avg={avg*1e3:.3f}ms algbw={bw:.2f}GB/s",
                         ranks=[0])


def configure(enabled=False, verbose=False, debug=False):
    global _COMMS_LOGGER
    _COMMS_LOGGER = CommsLogger(verbose=verbose, debug=debug) if enabled else None


def get_comms_logger():
    return _COMMS_LOGGER


def log_summary():
    if _COMMS_LOGGER is not None:
        _COMMS_LOGGER.log_all()


def timed_host_op(name, fn, tensor, *args, **kwargs):
    """Run a host-path op with wall-clock timing into the comms logger
    AND the shared registry (``comm/host_bytes_per_s`` histogram +
    ``comm/host_bytes_total`` counter) — the achieved-bandwidth side of
    the collective accounting; only host-path ops can be wall-timed
    honestly (their ``block_until_ready`` is the benchmark's own sync,
    not a step-path one)."""
    if _COMMS_LOGGER is None:
        return fn(tensor, *args, **kwargs)
    t0 = time.time()
    out = fn(tensor, *args, **kwargs)
    out.block_until_ready()
    elapsed = time.time() - t0
    nbytes = tensor.size * tensor.dtype.itemsize
    _COMMS_LOGGER.append(name, elapsed, nbytes)
    reg = get_registry()
    reg.counter("comm/host_bytes_total").inc(int(nbytes))
    if elapsed > 0:
        reg.histogram("comm/host_bytes_per_s").observe(nbytes / elapsed)
    return out
