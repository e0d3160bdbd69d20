"""KV-cache tree plumbing for ragged decode and paged serving.

The flax "cache" collection produced by ``init_cache`` is a nested dict
whose attention units hold three leaves (models/layers.py SelfAttention):

- ``cached_key`` / ``cached_value``: ``[b, h, d, max_len]`` in K^T layout,
  or ``[L, b, h, d, max_len]`` when the blocks are nn.scan-stacked;
- ``cache_index``: the write position — scalar (``()`` / ``[L]``) on the
  classic equal-length path, or per-row (``[b]`` / ``[L, b]``) on the
  ragged/serving path.

A latent-attention unit (models/layers.py LatentAttention) holds ONE leaf
beside its index: ``cached_key`` ``[b, 1, d_latent, max_len]``, a token's
normalised compressed vector followed by its rotated shared key part.
Its values are the leading rows of the same leaf, so it has no
``cached_value``; every walker below takes the K/V leaves a unit has.

These helpers walk the tree by attention unit (any dict holding a
``cached_key``) so they stay correct for scanned, unrolled, and MoE
models without hard-coding the module hierarchy. All of them are pure
jnp functions, safe inside jit.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..serving.paging.allocator import NULL_PAGE

# a unit's K/V leaves beside the keys of the "kv_token" collection that
# carry a step's tokens for them
_KV_TOKENS = (("cached_key", "k"), ("cached_value", "v"))
_KV_KEYS = tuple(name for name, _ in _KV_TOKENS)
# int8 page pools carry one fp32 scale plane per KV leaf (serving int8
# KV pages): [num_pages, h, 1, page_len] — one scale per head per token,
# stored page-shaped so scatters and the paged-attention kernel address
# scales exactly like pages
_SCALE_KEYS = {"cached_key": "key_scale", "cached_value": "value_scale"}


def _as_dict(tree):
    """Unfreeze flax FrozenDicts into plain nested dicts (identity on
    dicts) so the walkers below can rebuild the tree structurally."""
    try:
        from flax.core import unfreeze
        return unfreeze(tree)
    except ImportError:
        return tree


def _is_attn_unit(d) -> bool:
    return isinstance(d, dict) and "cached_key" in d


def _kv_leaves(unit):
    """``(leaf name, its "kv_token" key)`` for the K/V leaves ``unit``
    holds: both for an attention unit, the keys alone for a latent
    one."""
    return [(name, tok) for name, tok in _KV_TOKENS if name in unit]


def is_latent_unit(d) -> bool:
    return _is_attn_unit(d) and "cached_value" not in d


def has_latent_units(tree) -> bool:
    """Whether the cache tree (or page pool) holds a latent-attention
    unit: what moves, quantizes or re-runs pages by the K/V geometry
    refuses such a pool by name (serving/paging/manager.py)."""
    found = []
    _map_units(tree, lambda u: found.append(is_latent_unit(u)) or u)
    return any(found)


def _map_units(cache, fn):
    """Rebuild ``cache`` with ``fn(unit_dict) -> unit_dict`` applied to
    every attention unit."""
    cache = _as_dict(cache)

    def walk(node):
        if _is_attn_unit(node):
            return fn(dict(node))
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache)


def kv_leaves(tree) -> list:
    """Every K/V leaf (and int8 scale plane) of the tree's attention
    units, in walking order: what the pool's page bytes and its K/V type
    are read from — a state unit's leaves are not among them."""
    found = []

    def grab(unit):
        found.extend(unit[k] for k in _KV_KEYS + tuple(_SCALE_KEYS.values())
                     if k in unit)
        return unit

    _map_units(tree, grab)
    return found


def cache_max_len(cache) -> int:
    """The allocated sequence capacity (static python int)."""
    found = []

    def probe(unit):
        found.append(int(unit["cached_key"].shape[-1]))
        return unit

    _map_units(cache, probe)
    if not found:
        raise ValueError("no attention cache units found in the cache tree")
    return found[0]


def set_cache_index(cache, lengths):
    """Overwrite every ``cache_index`` with per-row ``lengths`` ([b] int32).

    Scan-stacked units get ``[L, b]`` (every layer shares the same row
    lengths); unstacked units get ``[b]``. The K/V leaves are untouched.
    """
    lengths = jnp.asarray(lengths, jnp.int32)

    def setter(unit):
        stacked = unit["cached_key"].ndim == 5
        if stacked:
            n_layers = unit["cached_key"].shape[0]
            unit["cache_index"] = jnp.broadcast_to(
                lengths, (n_layers,) + lengths.shape)
        else:
            unit["cache_index"] = lengths
        return unit

    return _map_units(cache, setter)


# ---------------------------------------------------------------------------
# paged-pool plumbing (serving/paging): the same cache-tree walkers applied
# to a page pool — a cache tree whose "batch" axis is physical pages and
# whose "sequence" axis is one page. Pure jnp, safe inside jit; page 0 is
# the reserved null page (what masked/unowned table entries and writes
# name: gathered as garbage, never written by the append).
# ---------------------------------------------------------------------------

def cache_page_len(pool) -> int:
    """Tokens per page of a page pool (static python int)."""
    return cache_max_len(pool)


def pool_is_quantized(pool) -> bool:
    """True when the page pool stores int8 KV pages (+ scale planes)."""
    found = []

    def probe(unit):
        found.append("key_scale" in unit)
        return unit

    _map_units(pool, probe)
    return bool(found) and found[0]


def quantize_page_pool(pool):
    """Convert a freshly initialized (zeroed) page pool to int8 storage:
    every KV leaf becomes int8 zeros plus an fp32 scale plane of zeros
    (``[pages, h, 1, page_len]``; ``[L, ...]`` scan-stacked). Page bytes
    halve vs bf16 (quarter vs fp32) — the density lever on top of
    paging. Scatters quantize on write; gathers and the paged-attention
    kernel dequantize on read."""

    def convert(unit):
        out = dict(unit)
        for name in _KV_KEYS:
            kv = unit[name]
            scale_shape = kv.shape[:-2] + (1,) + kv.shape[-1:]
            out[name] = jnp.zeros(kv.shape, jnp.int8)
            out[_SCALE_KEYS[name]] = jnp.zeros(scale_shape, jnp.float32)
        return out

    return _map_units(pool, convert)


def _quantize_kv(leaf):
    """Symmetric per-token-per-head int8: absmax over the head_dim axis
    (axis -2 of the K^T layout ``[..., h, d, n]``) -> (int8 leaf, fp32
    scale ``[..., h, 1, n]``). The shared quantization rule for token
    and chunk scatters — one definition, or scatter and kernel dequant
    silently disagree."""
    x = leaf.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-2, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def gather_pages(pool, page_table, scalar_index: bool = False,
                 dequant_dtype=None):
    """Materialize the contiguous per-slot view of a paged pool.

    ``page_table`` is ``[slots, max_pages]`` int32 (physical page per
    logical page; unowned entries hold the null page). Returns a cache
    tree shaped exactly like the classic slot cache —
    ``[slots, h, d, max_pages * page_len]`` per unit — so the existing
    attention decode path runs unchanged on top of it. ``cache_index``
    comes back zeroed per-row (``[slots]``), or scalar-mode when
    ``scalar_index`` (the single-row chunk-prefill form); callers set the
    real lengths via ``set_cache_index``.

    int8 pools dequantize during the gather (``dequant_dtype`` — the
    model's KV compute dtype; fp32 when unset), so the view the dense
    attention/prefill paths see is an ordinary float cache."""
    page_table = jnp.asarray(page_table, jnp.int32)
    slots, max_pages = page_table.shape

    def gather(unit):
        out = {}
        stacked = unit["cached_key"].ndim == 5
        quant = "key_scale" in unit
        for name, _ in _kv_leaves(unit):
            kv = unit[name]
            if stacked:
                g = kv[:, page_table]              # [L, s, m, h, d, p]
                if quant:
                    sc = unit[_SCALE_KEYS[name]][:, page_table]
                    g = (g.astype(jnp.float32) * sc).astype(
                        dequant_dtype or jnp.float32)
                g = g.transpose(0, 1, 3, 4, 2, 5)  # [L, s, h, d, m, p]
                out[name] = g.reshape(g.shape[:4] + (-1,))
            else:
                g = kv[page_table]                 # [s, m, h, d, p]
                if quant:
                    sc = unit[_SCALE_KEYS[name]][page_table]
                    g = (g.astype(jnp.float32) * sc).astype(
                        dequant_dtype or jnp.float32)
                g = g.transpose(0, 2, 3, 1, 4)     # [s, h, d, m, p]
                out[name] = g.reshape(g.shape[:3] + (-1,))
        n_layers = unit["cached_key"].shape[0] if stacked else None
        if scalar_index:
            idx_shape = (n_layers,) if stacked else ()
        else:
            idx_shape = (n_layers, slots) if stacked else (slots,)
        out["cache_index"] = jnp.zeros(idx_shape, jnp.int32)
        return out

    return _map_units(pool, gather)


def _walk_with(pool, src, fn):
    """Rebuild ``pool`` applying ``fn(pool_unit, src_subtree)`` at every
    attention unit, where ``src`` mirrors the pool's tree structure
    (e.g. the "kv_token" collection emitted by models/layers.py)."""
    pool = _as_dict(pool)
    src = _as_dict(src)

    def walk(dst, s):
        if _is_attn_unit(dst):
            return fn(dict(dst), s)
        if isinstance(dst, dict) and not _is_state_unit(dst):
            # a state unit has nothing for ``fn`` and may have nothing in
            # ``src`` (a mixer publishes no token of a decode step), nor
            # may a layer that holds such units alone
            return {k: v if _is_state_unit(v) else walk(v, s.get(k, {}))
                    for k, v in dst.items()}
        return dst

    return walk(pool, src)


def extract_token_kv(cache, idx):
    """Per-unit single-token K/V read from a contiguous cache view:
    row ``b``'s entry at position ``idx[b]`` — the fallback source for
    the pool scatter when the module does not publish a "kv_token"
    collection. Leaves come back ``[b, h, d, 1]`` (``[L, b, h, d, 1]``
    stacked), matching the kv_token layout."""
    idx = jnp.asarray(idx, jnp.int32)

    def extract(unit):
        stacked = unit["cached_key"].ndim == 5
        sel = (idx[None, :, None, None, None] if stacked
               else idx[:, None, None, None])
        return {tok: jnp.take_along_axis(unit[name], sel, axis=-1)
                for name, tok in _kv_leaves(unit)}

    # rebuild a token tree with the cache's structure, one {"k","v"} dict
    # per attention unit (the kv_token collection's layout)
    cache = _as_dict(cache)

    def walk(node):
        if _is_attn_unit(node):
            return extract(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache)


def _append_rows(dst, val, tokens, count):
    """Write ``count`` tokens of ``val`` into ``dst`` in place. Token
    ``i`` is row ``b`` of ``val``'s ``rows`` and lands on lane ``o`` of
    page ``p``, all three in one number, ``tokens[i] = (p * page_len +
    o) * rows + b``: ``dst[..., p, :, :, o] = val[..., b, :, :, 0]``.
    The page (of every layer, when stacked) is read, one lane of it
    replaced, and the page written back where it was. A scatter over the
    page and the in-page offset — the lane dimension of the K^T layout —
    makes XLA:TPU change the whole pool's layout and change it back,
    every step; this touches the pages it writes and nothing else, so a
    donated pool stays the donated pool. One trip moves a page of every
    layer at the memory's speed (38 us for 24 x 512 KiB on the v5e), so
    the loop makes ``count`` of them, a number the program reads off its
    own arguments, and not one per slot. One number a trip, because the
    trip waits for each one it reads: with page, lane and row read
    apart a trip took 1.9 us longer than the loop over every slot's,
    which a full batch would pay 64 times a step. A token-sized write
    would not be cheaper than a trip: tokens lie on the lane dimension,
    so one token is one lane of every (16, 128) tile of the page
    (ROADMAP 1.0a')."""
    page_axis = dst.ndim - 4                      # 1 when layer-stacked
    rows, page_len = val.shape[page_axis], dst.shape[-1]
    if dst.shape[page_axis] * page_len * rows >= 2 ** 31:
        raise ValueError(
            f"{dst.shape[page_axis]} pages of {page_len} tokens written "
            f"from {rows} rows: a token's place in the pool and its row "
            "do not fit one int32")
    one_page = dst.shape[:page_axis] + (1,) + dst.shape[page_axis + 1:]
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (1,) * (dst.ndim - 1) + (page_len,), dst.ndim - 1)

    def append_token(i, out):
        where, b = jnp.divmod(tokens[i], rows)
        p, o = jnp.divmod(where, page_len)
        at = (0,) * page_axis + (p, 0, 0, 0)
        page = jax.lax.dynamic_slice(out, at, one_page)
        row = jax.lax.dynamic_slice_in_dim(val, b, 1, axis=page_axis)
        page = jnp.where(lane == o, row.astype(out.dtype), page)
        return jax.lax.dynamic_update_slice(out, page, at)

    return jax.lax.fori_loop(0, count, append_token, dst)


def scatter_token_pages(pool, token_tree, pages, offsets):
    """Append one decode step's K/V to the pool in place: row ``b``'s
    token lands at ``pool[pages[b], :, :, offsets[b]]`` (every layer of
    a stacked pool at once). Distinct active rows own distinct tail
    pages by construction. A row the caller routes to the null page —
    one that does not decode, or a write past a slot's budget — holds
    no token: it is not written, the null page stays as it was, and the
    append costs what the rows that are written cost."""
    pages = jnp.asarray(pages, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    rows, page_len = pages.shape[0], cache_page_len(pool)
    # the rows that hold a token, in row order, ahead of the others
    live = pages != NULL_PAGE
    first = jnp.argsort(~live, stable=True)
    tokens = ((pages * page_len + offsets) * rows + jnp.arange(rows))[first]
    count = jnp.sum(live, dtype=jnp.int32)

    def scatter(unit, tok):
        out = dict(unit)
        quant = "key_scale" in unit
        for name, leaf in ((n, tok[t]) for n, t in _kv_leaves(unit)):
            if quant:
                # quantize on write: the token's K/V arrives in compute
                # precision (kv_token), lands int8 with its scale plane
                leaf, sc = _quantize_kv(leaf)
                sname = _SCALE_KEYS[name]
                out[sname] = _append_rows(unit[sname], sc, tokens, count)
            out[name] = _append_rows(unit[name], leaf, tokens, count)
        return out

    return _walk_with(pool, token_tree, scatter)


def scatter_chunk_pages(pool, token_tree, page_run):
    """Scatter a page-aligned prefill chunk into the pool. ``token_tree``
    leaves are ``[1, h, d, chunk]`` (``[L, 1, h, d, chunk]`` stacked)
    with ``chunk`` an exact multiple of ``page_len``; ``page_run`` is the
    ``chunk // page_len`` physical pages the chunk covers, in order."""
    page_run = jnp.asarray(page_run, jnp.int32)
    n_t = page_run.shape[0]

    def scatter(unit, tok):
        out = dict(unit)
        page_len = unit["cached_key"].shape[-1]
        quant = "key_scale" in unit
        for name, leaf in ((n, tok[t]) for n, t in _kv_leaves(unit)):
            kv = unit[name]
            writes = [(name, kv, leaf)]
            if quant:
                leaf, sc = _quantize_kv(leaf)
                sname = _SCALE_KEYS[name]
                writes = [(name, kv, leaf), (sname, unit[sname], sc)]
            for wname, dst, val in writes:
                d_ = dst.shape[-2]                         # d, or 1 (scale)
                if dst.ndim == 5:
                    n_l, _, h, _, _ = dst.shape
                    v = val[:, 0].reshape(n_l, h, d_, n_t, page_len)
                    v = v.transpose(0, 3, 1, 2, 4)         # [L, n_t, h, d, p]
                    out[wname] = dst.at[:, page_run].set(v)
                else:
                    _, h, _, _ = dst.shape
                    v = val[0].reshape(h, d_, n_t, page_len)
                    v = v.transpose(2, 0, 1, 3)            # [n_t, h, d, p]
                    out[wname] = dst.at[page_run].set(v)
        return out

    return _walk_with(pool, token_tree, scatter)


def export_pages(pool, page_ids):
    """Read ``page_ids``'s K/V contents (and scale planes, for int8
    pools) out of the pool as host numpy arrays — the device half of the
    fleet's page-granular prefill/decode handoff (serving/fleet/). One
    record per attention unit, in ``_map_units`` traversal order (a
    deterministic walk both ends share), each leaf
    ``[n, h, d|1, page_len]`` (``[L, n, ...]`` scan-stacked). A host
    sync by design: the handoff is a host-mediated page transfer."""
    ids = np.asarray(page_ids, np.int32)
    units = []

    def grab(unit):
        stacked = unit["cached_key"].ndim == 5
        rec = {}
        for name in _KV_KEYS + tuple(_SCALE_KEYS.values()):
            leaf = unit.get(name)
            if leaf is None:
                continue
            rec[name] = np.asarray(leaf[:, ids] if stacked else leaf[ids])
        units.append(rec)
        return unit

    _map_units(pool, grab)
    return units


def import_pages(pool, page_ids, units):
    """Write ``export_pages`` records into ``page_ids`` of (a structurally
    identical) ``pool`` — the receiving half of the page handoff. Pure
    ``.at[].set`` dispatches outside any jit (the page-table-update
    pattern): shapes never change, so every compiled paged program stays
    cached. ``units`` must come from a pool with the same layout and
    quantization mode (the engine validates the wire format first)."""
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    it = iter(units)

    def put(unit):
        rec = next(it)
        stacked = unit["cached_key"].ndim == 5
        out = dict(unit)
        for name, data in rec.items():
            if name not in unit:
                raise ValueError(
                    f"handoff page payload carries {name!r} but the "
                    "receiving pool has no such plane — quantization "
                    "modes differ between replicas")
            leaf = unit[name]
            out[name] = (leaf.at[:, ids].set(data) if stacked
                         else leaf.at[ids].set(data))
        return out

    return _map_units(pool, put)


def make_paged_view(pool, page_table, lengths):
    """The variable collections the KERNEL-path paged decode hands to
    ``module.apply`` beside ``params``: ``{"cache": ..., "kv_pool": ...}``.

    ``kv_pool`` is the pool itself, leaf for leaf (int8 + scale planes
    included), read-only: a scanned model broadcasts it through its
    layer scan, so the stacked ``[L, pages, h, d, page_len]`` buffers
    reach the paged-attention kernel whole and loop-invariant, and the
    program never slices or restacks them. ``cache`` holds the small
    per-step state only: the ``page_table`` (``[slots, max_pages]``),
    per-row ``lengths`` as ``cache_index`` (how many pooled tokens the
    kernel walks for the row and nothing else — positions travel beside
    it — so the decode program hands 0 for a row that does not decode),
    and for scan-stacked units the ``layer`` index (``arange(L)``) — each
    with a leading ``[L]`` there, so nn.scan hands every layer its own
    copy. SelfAttention
    detects the ``page_table`` variable structurally and runs the
    kernel straight over the pool — no contiguous view is gathered."""
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    def small_state(unit):
        if unit["cached_key"].ndim != 5:
            return {"page_table": page_table, "cache_index": lengths}
        n_layers = unit["cached_key"].shape[0]
        return {
            "page_table": jnp.broadcast_to(
                page_table, (n_layers,) + page_table.shape),
            "cache_index": jnp.broadcast_to(
                lengths, (n_layers,) + lengths.shape),
            "layer": jnp.arange(n_layers, dtype=jnp.int32),
        }

    return {"cache": _map_units(pool, small_state), "kv_pool": _as_dict(pool)}


# ---------------------------------------------------------------------------
# recurrent state beside the pages: a layer whose "cache" unit is a
# fixed-size state (``conv_state [batch, ...]``, and for a state-space
# mixer ``ssm_state [batch, ...]`` beside it) and not keys and values. In
# a page pool every such leaf is kept a SLOT (``[slots, ...]``: each
# slot's state where its request now stands, what a decode step reads and
# advances), and the states at page ends, from which a prefix hit starts,
# in one of two layouts:
#
# - **a state a page** (models/layers.py ShortConv: 16 KB a page a layer):
#   ``page_state [pages, ...]``, the state at the END of each page's last
#   token, written by the prefill chunk that filled the page. A chunk
#   starts on a page boundary and takes the state of the page before it,
#   its own request's or one shared through the prefix cache, and position
#   0 takes zeros: a hit restores the state with no host work.
# - **a snapshot pool** (models/layers.py Mamba2Mixer: a unit that holds
#   ``ssm_state``, 4 MB a sequence a layer, 16 times the K/V of the page
#   it would be stored with): ``snapshots {leaf: [entries, ...]}``, far
#   fewer entries than pages, with the page -> entry table kept by the
#   host (serving/paging/snapshots.py, which has the rule for which page
#   ends get one). A request's first chunk starts from the entry the host
#   names (a prefix hit), from zeros at position 0, and every later chunk
#   from its slot's own state; a chunk writes the states at its page ends
#   (the mixer's own ``chunk_states``, its chunk being a page) to the
#   entries the host names, entry 0 standing for "none" (never read).
# - **none** (a pool that holds a ring unit): nothing is kept of a page's
#   end. A **ring unit** is a window-attention layer's keys and values,
#   ``ring_key`` / ``ring_value [slots, h, d, window]`` (models/layers.py
#   DifferentialAttention: token ``t`` on lane ``t mod window``), a slot's
#   like every state above: the decode step writes its token's column in
#   place and the contiguous decode kernel reads the ring as it stands, a
#   chunk takes its slot's row and puts it back. A prefix hit would have
#   to restore the window's contents at the hit's depth (21 MB a sequence
#   at 8 layers x 512 tokens x 5 KB), which nothing stores: the manager
#   refuses the prefix cache for such a model by name, and so no unit of
#   its pool, a mixer's beside the rings neither, keeps page-end states.
# ---------------------------------------------------------------------------

_STATE_KEY = "conv_state"
_MATRIX_STATE_KEY = "ssm_state"
_PAGE_STATE_KEY = "page_state"
_SNAPSHOTS_KEY = "snapshots"
_RING_KEYS = ("ring_key", "ring_value")
_SLOT_KEYS = (_STATE_KEY, _MATRIX_STATE_KEY) + _RING_KEYS
NULL_SNAPSHOT = 0


def _is_ring_unit(d) -> bool:
    return isinstance(d, dict) and _RING_KEYS[0] in d


def _is_state_unit(d) -> bool:
    """A unit kept a slot: a recurrent state, or a window's ring."""
    return isinstance(d, dict) and (_STATE_KEY in d or _is_ring_unit(d))


def _slot_leaves(unit) -> dict:
    return {k: unit[k] for k in _SLOT_KEYS if k in unit}


def _walk_state(tree, fn, *srcs):
    """Rebuild ``tree`` with ``fn(unit, *src_units)`` at every state unit;
    each of ``srcs`` mirrors the tree's structure at least down to those
    units."""

    def walk(node, ss):
        if _is_state_unit(node):
            return fn(dict(node), *ss)
        if isinstance(node, dict) and not _is_attn_unit(node):
            # a source may lack a layer that published nothing
            return {k: walk(v, [s.get(k, {}) for s in ss]
                            if isinstance(v, dict) else ss)
                    for k, v in node.items()}
        return node

    return walk(_as_dict(tree), [_as_dict(s) for s in srcs])


def state_units(tree) -> list:
    """The tree's state units, in walking order: a model without
    recurrent state has none, and the programs' branch on
    ``len(state_units(pool))`` is structural — such a model traces
    exactly what it traced before."""
    found = []
    _walk_state(tree, lambda u: found.append(u) or u)
    return found


def has_recurrent_state(tree) -> bool:
    return len(state_units(tree)) > 0


def has_ring_units(tree) -> bool:
    """Whether the cache tree (or pool) holds a window layer's ring."""
    return any(_is_ring_unit(u) for u in state_units(tree))


def has_snapshot_pool(tree) -> bool:
    """Whether a state unit of the pool keeps its page-end states in a
    snapshot pool (it holds a matrix state, beside no ring)."""
    return any(_SNAPSHOTS_KEY in u for u in state_units(tree))


def has_page_states(tree) -> bool:
    """Whether a state unit of the pool keeps a state a page."""
    return any(_PAGE_STATE_KEY in u for u in state_units(tree))


def describe_state(tree) -> str:
    """What the model keeps beside its K/V pages, for a refusal."""
    if has_ring_units(tree):
        return ("a ring of a window's keys and values a slot in each "
                "window layer, and a state-space mixer's state a slot "
                "(nothing of either at a page's end)")
    unit = state_units(tree)[0]
    if _MATRIX_STATE_KEY in unit:
        return ("a state-space mixer's state (a convolution's last columns "
                "and a matrix state a slot, with snapshots of some page "
                "ends)")
    return "a convolution state (a slot's, and one at each page's end)"


def init_page_pool(module, params, num_pages: int, page_len: int,
                   num_slots: int = 0, snapshots: int = 0):
    """Allocate a paged KV pool: ``[num_pages, h, d, page_len]`` per
    attention unit (``[L, num_pages, ...]`` scan-stacked) — shape-only
    init, no FLOPs burned. A state unit gets its leaves a slot
    (``num_slots``) and its page-end states in its layout (above):
    ``page_state`` over the pages, or ``snapshots`` over ``snapshots``
    entries and the null one, or nothing in a pool with a ring unit."""
    from .generation import cache_shapes
    shapes = cache_shapes(module, params, num_pages, page_len)
    slots_only = has_ring_units(shapes)

    def state(unit):
        def zeros(leaf, rows):
            return jnp.zeros((rows,) + leaf.shape[1:], leaf.dtype)
        out = {k: zeros(v, num_slots) for k, v in unit.items()}
        if slots_only:
            return out
        if _MATRIX_STATE_KEY in unit:
            out[_SNAPSHOTS_KEY] = {k: zeros(v, snapshots + 1)
                                   for k, v in unit.items()}
        else:
            out[_PAGE_STATE_KEY] = zeros(unit[_STATE_KEY], num_pages)
        return out

    pool = _walk_state(shapes, state)
    return jax.tree.map(
        lambda s: (jnp.zeros(s.shape, s.dtype)
                   if isinstance(s, jax.ShapeDtypeStruct) else s), pool)


def slot_state_view(cache):
    """``cache`` as the module takes it: a state unit's slot leaves
    alone."""
    return _walk_state(cache, _slot_leaves)


def chunk_state_view(cache, pool, prev_page, fresh, slot=None, restore=None):
    """A single-row cache for a prefill chunk. A unit with a state a page
    starts from the state at the end of ``prev_page`` (the physical page
    before the chunk's first); one with a snapshot pool from entry
    ``restore`` when that is not negative (a prefix hit's first chunk),
    else from ``slot``'s own state (the chunk before left it there);
    either from zeros when ``fresh`` (the chunk starts at position 0)."""

    def row(leaf, at):
        return jax.lax.dynamic_index_in_dim(leaf, at, axis=0, keepdims=True)

    def start(_, unit):
        if _PAGE_STATE_KEY in unit:
            return {_STATE_KEY: jnp.where(
                fresh, 0.0, row(unit[_PAGE_STATE_KEY], prev_page))}
        if _SNAPSHOTS_KEY not in unit:
            # kept a slot and nowhere else: the chunk before left it
            return {k: jnp.where(fresh, jnp.zeros((), leaf.dtype),
                                 row(leaf, slot))
                    for k, leaf in _slot_leaves(unit).items()}
        return {k: jnp.where(
            fresh, jnp.zeros((), leaf.dtype), jnp.where(
                restore >= 0,
                row(unit[_SNAPSHOTS_KEY][k], jnp.maximum(restore, 0)),
                row(leaf, slot)))
            for k, leaf in _slot_leaves(unit).items()}

    return _walk_state(cache, start, pool)


def store_decode_state(pool, cache_out):
    """After a decode step: every slot's state as the module left it (a
    row that held no token kept its own)."""
    return _walk_state(
        pool, lambda unit, out: {**unit, **_slot_leaves(out)}, cache_out)


def store_chunk_state(pool, cache_out, token_tree, slot, page_run,
                      snap_run=None):
    """After a prefill chunk: the slot's state is the row's (the state
    after the chunk's last live token), and the states at the chunk's
    page ends go where the unit's layout keeps them. A state a page: each
    page of ``page_run`` gets the state at its end, cut from the unit's
    ``trail`` (the chunk's starting state followed by one column a
    position): page ``i`` of the chunk ends ``(i + 1) * page_len``
    columns in. A snapshot pool: the state at page ``i``'s end (the
    mixer's ``chunk_states``) goes to entry ``snap_run[i]``, the null
    entry where the host wants none. A page the chunk only partly fills
    gets columns of its padding: such a page is never shared, and no
    chunk ever starts after it."""
    n_t = page_run.shape[0]

    def store(unit, out, tok):
        new = {k: leaf.at[slot].set(out[k][0])
               for k, leaf in _slot_leaves(unit).items()}
        if _SNAPSHOTS_KEY in unit:
            ends = tok["chunk_states"]
            if ends[_STATE_KEY].shape[1] != n_t:
                raise ValueError(
                    f"the mixer hands back {ends[_STATE_KEY].shape[1]} "
                    f"chunk-end states for a chunk of {n_t} pages: its "
                    "chunk size has to be the page length")
            new[_SNAPSHOTS_KEY] = {
                k: leaf.at[snap_run].set(ends[k][0])
                for k, leaf in unit[_SNAPSHOTS_KEY].items()}
            return new
        if _PAGE_STATE_KEY not in unit:
            return new
        trail = tok["trail"][0]                    # [taps-1 + chunk, d]
        width = unit[_STATE_KEY].shape[1]
        page_len = (trail.shape[0] - width) // n_t
        ends = jnp.stack([trail[(i + 1) * page_len:(i + 1) * page_len + width]
                          for i in range(n_t)])
        new[_PAGE_STATE_KEY] = unit[_PAGE_STATE_KEY].at[page_run].set(ends)
        return new

    return _walk_state(pool, store, cache_out, token_tree)


def state_bytes(pool) -> int:
    """Resident bytes of the recurrent state and the rings, slots and
    page ends (or snapshots) together (0 for a model without)."""
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for unit in state_units(pool)
               for leaf in jax.tree.leaves(unit))
