"""Shape-keyed Pallas kernel tuning cache.

Per-shape block-size (tiling) choices are worth double-digit MFU on TPU
(the TPU-v3 MLPerf scaling work sweeps per-shape tilings; our own flash
kernel comments record 82 TFLOPS at s1024 with constants hand-picked at
ONE shape). This module makes those choices table-driven:

- a **tuning entry** maps a canonical shape key (kernel / structure /
  sq / sk / head_dim / dtype / causality) to the block sizes the kernel
  dispatch should use, e.g. ``{"block_q": 512, "block_k": 512}``;
- entries are layered, first hit wins:
  1. an in-process table installed with ``set_tuning_table`` (tests, the
     sweep harness timing candidates),
  2. a JSON artifact named by ``$DS_TPU_KERNEL_TUNING_CACHE``
     (fleet-specific, regenerated by ``bin/ds_tpu_bench kernels`` on
     hardware),
  3. the committed default table (``flash_tuning_defaults.json``) beside
     this file;
- a miss everywhere returns ``{}`` and the dispatch falls back to its
  hand-picked module constants, so kernels never fail for lack of a
  table.

Lookups happen at TRACE time (static shapes -> pure dict lookup), so a
table change affects the next trace, not compiled executables; clear jit
caches (or retrace) after swapping tables mid-process.

Artifact format (``save_artifact``) — one JSON object::

    {"format": "ds-tpu-kernel-tuning-v1",
     "device": "<device_kind or cpu-interpret>",
     "entries": {"<key>": {"block_q": 512, "block_k": 512,
                           "ms": 1.23}, ...},
     ...free-form metadata...}

Only ``block_q``/``block_k`` are consumed by the dispatch; extra fields
(measured ms, notes) ride along for humans.
"""

import functools
import json
import os
import threading

FORMAT = "ds-tpu-kernel-tuning-v1"
ENV_VAR = "DS_TPU_KERNEL_TUNING_CACHE"
DEFAULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "flash_tuning_defaults.json")

_lock = threading.Lock()
_runtime_table = {}

# trace-time dispatch probe: {kernel: {structure: {"key":…, "source":…,
# "block_q":…, "block_k":…}}} — lets tests (and the sweep harness) see
# which cache entry a dispatch actually consumed
_last_dispatch = {}


def make_key(kernel, structure, *, sq, sk, d, dtype, causal):
    """Canonical shape key. ``dtype`` is anything jnp.dtype accepts."""
    import jax.numpy as jnp
    dt = jnp.dtype(dtype).name
    mode = "causal" if causal else "full"
    return f"{kernel}/{structure}/sq{int(sq)}_sk{int(sk)}_d{int(d)}_{dt}_{mode}"


def _coerce_entries(obj):
    """Accept a raw entries dict, a full artifact dict, or a file path."""
    if isinstance(obj, str):
        with open(obj) as f:
            obj = json.load(f)
    if isinstance(obj, dict) and "entries" in obj:
        obj = obj["entries"]
    if not isinstance(obj, dict):
        raise ValueError(f"tuning table must be a dict of key->entry, got "
                         f"{type(obj).__name__}")
    return {str(k): dict(v) for k, v in obj.items()}


def set_tuning_table(table=None):
    """Install (or with ``None`` clear) the highest-precedence in-process
    tuning table. ``table``: entries dict, artifact dict, or a path."""
    global _runtime_table
    entries = {} if table is None else _coerce_entries(table)
    with _lock:
        _runtime_table = entries


class tuning_table:
    """Context manager form of ``set_tuning_table`` (tests, sweeps)."""

    def __init__(self, table):
        self._table = table

    def __enter__(self):
        with _lock:
            self._saved = dict(_runtime_table)
        set_tuning_table(self._table)
        return self

    def __exit__(self, *exc):
        set_tuning_table(self._saved)
        return False


@functools.lru_cache(maxsize=None)
def _file_entries(path, mtime):
    """mtime rides in the cache key so an updated artifact re-loads."""
    try:
        return _coerce_entries(path)
    except (OSError, ValueError) as e:
        from ...utils.logging import warn_once
        warn_once(f"kernel tuning cache {path!r} unreadable ({e}) — ignored")
        return {}


def _load_file(path):
    if not path:
        return {}
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    return _file_entries(path, mtime)


def lookup(kernel, structure, *, sq, sk, d, dtype, causal):
    """First-hit entry for the shape key across the table layers; ``{}``
    on a full miss (caller falls back to its constants). The consumed
    (key, source, blocks) is recorded for ``last_dispatch``."""
    key = make_key(kernel, structure, sq=sq, sk=sk, d=d, dtype=dtype,
                   causal=causal)
    with _lock:
        runtime = _runtime_table
    layers = (("runtime", runtime),
              ("env", _load_file(os.environ.get(ENV_VAR))),
              ("defaults", _load_file(DEFAULTS_PATH)))
    for source, table in layers:
        entry = table.get(key)
        if entry:
            return dict(entry), key, source
    return {}, key, None


def record_dispatch(kernel, structure, key, source, **blocks):
    """Called by the kernel dispatch with the blocks it settled on
    (post-validation, so this reflects what actually runs).
    ``interpret`` says how a Pallas call in this dispatch lowers: False
    = compiled by Mosaic (platform tpu), True = interpreted."""
    from ._common import interpret_mode
    with _lock:
        _last_dispatch.setdefault(kernel, {})[structure] = dict(
            key=key, source=source or "constants",
            interpret=interpret_mode(), **blocks)


def last_dispatch(kernel="flash_attention"):
    """{structure: {key, source, block_*}} of the most recent trace-time
    dispatches for ``kernel`` — a debug/test probe, not an API."""
    with _lock:
        return {k: dict(v) for k, v in _last_dispatch.get(kernel, {}).items()}


def clear_last_dispatch():
    with _lock:
        _last_dispatch.clear()


def save_artifact(path, entries, **meta):
    """Write a tuning artifact (the sweep harness's output; also usable to
    seed ``$DS_TPU_KERNEL_TUNING_CACHE`` by hand). Returns the artifact
    dict."""
    artifact = {"format": FORMAT, **meta,
                "entries": {str(k): dict(v) for k, v in entries.items()}}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=False)
        f.write("\n")
    os.replace(tmp, path)
    return artifact


def load_artifact(path):
    """Read an artifact; returns the full dict (entries under "entries")."""
    with open(path) as f:
        obj = json.load(f)
    if obj.get("format") != FORMAT:
        raise ValueError(f"{path}: not a {FORMAT} artifact "
                         f"(format={obj.get('format')!r})")
    return obj
