"""Paged-KV manager: the device page pool + host page-table ownership.

This is the serving engine's memory model. Every layer's K/V lives
in a global pool ``[num_pages, h, d, page_len]`` and each slot holds a
dense int32 page table ``[num_slots, max_pages]``. HBM scales with
*realized* context (pages actually allocated), not with
``num_slots * max_len`` — the density lever DeepSpeed-Inference
(arXiv:2207.00032) attributes serving-at-scale wins to, applied under
the TPU compile-once discipline:

- the page table is a fixed-shape array operand, so admissions and
  frees change DATA, never compiled shapes;
- decode is ONE compiled program, ever, and on the kernel path it
  leaves the pool where it is: the pool is a donated argument that
  rides ``module.apply`` as a read-only collection (``inference/
  cache.py make_paged_view``; a scanned model broadcasts it through its
  layer scan, never slicing or restacking it), the paged-attention
  kernel reads each slot's pages out of it by layer, and the step's K/V
  token is appended to its page in place — the program's output pool is
  its input buffer. The gather path (CPU default, unaligned pages)
  gathers each slot's pages into the classic contiguous view inside
  the program (``gather_pages``), runs the unchanged attention path,
  and appends the same way;
- prefill runs in page-aligned chunks through a single gathered row,
  one jit specialization per chunk-length bucket, interleaved between
  decode iterations by the engine (chunked prefill).

Allocation policy: a request's full token budget
(``prompt + max_new_tokens``) is allocated at admission. Conservative
on purpose — no decode-time page faults, no preemption machinery, fully
deterministic — while keeping the density win (budgets are realized
request sizes, not ``max_len``). Prefix-cache hits shrink the
allocation further: shared pages are referenced, not copied.
"""

from typing import Any, List, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ...inference.cache import (NULL_SNAPSHOT, _kv_leaves, cache_page_len,
                                chunk_state_view, describe_state,
                                export_pages, extract_token_kv, gather_pages,
                                has_latent_units, has_page_states,
                                has_recurrent_state, has_ring_units,
                                has_snapshot_pool, import_pages,
                                init_page_pool, kv_leaves, make_paged_view,
                                pool_is_quantized, quantize_page_pool,
                                scatter_chunk_pages, scatter_token_pages,
                                set_cache_index, slot_state_view,
                                state_bytes, state_units, store_chunk_state,
                                store_decode_state)
from ...inference.generation import _sample_impl, apply_decode
from ...observability.programs import track_program
from ...observability.trace import span as _span
from ...utils.logging import log_dist
from .allocator import NULL_PAGE, PageAllocator
from .config import CHUNK_PAGES
from .prefix import PrefixCache
from .snapshots import SnapshotTable


def _token_tree(vars_out, cache, idx):
    """The step's K/V to scatter: the module's published "kv_token"
    collection when present (models/layers.py), else sliced from the
    post-apply cache view. The choice is structural — decided at trace
    time from the tree, never from runtime values."""
    tok = vars_out.get("kv_token")
    has_tok = tok is not None and len(jax.tree.leaves(tok)) > 0
    if has_tok:
        return tok
    return extract_token_kv(cache, idx)


def _chunk_tree_from_cache(cache, start, chunk):
    """Fallback chunk K/V: slice ``[start, start + chunk)`` from the
    post-apply row view when no kv_token collection was published."""

    def walk(node):
        if isinstance(node, dict) and "cached_key" in node:
            return {tok: jax.lax.dynamic_slice_in_dim(
                        node[name], start, chunk, axis=-1)
                    for name, tok in _kv_leaves(node)}
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    try:
        from flax.core import unfreeze
        cache = unfreeze(cache)
    except ImportError:
        pass
    return walk(cache)


def _paged_decode_iter_impl(module, params, pool, page_table, state, rng, it,
                            eos_id, t, k, p, param_transform, greedy, has_k,
                            has_p, use_kernel=False, dequant_dtype=None):
    """One masked decode step over the full slot batch: every slot,
    active or not, runs the same static-shape computation; an active
    slot appends at its own length, attends over its own valid prefix
    and completes on eos or an exhausted budget, an inactive slot's
    output is masked to -1.

    ``use_kernel`` (static — one compiled program per engine either
    way): the paged-attention kernel consumes the pool + page table IN
    PLACE via ``make_paged_view`` — neither a contiguous per-slot view
    nor a layer's slice of the pool is ever materialized. Off-kernel,
    the PR-6 gather path runs unchanged: gather pages -> contiguous
    view (int8 pools dequantize to ``dequant_dtype`` during the gather)
    -> the unchanged attention path. Both append the new token's K/V to
    each active slot's tail page in place (quantized on write for int8
    pools); an inactive slot is routed to the null page, which the
    append does not write: it makes one trip per active slot, so its
    cost follows ``active`` as the kernel's lengths do. ``pool`` is
    donated and is the buffer the returned pool lives in: nothing
    between the argument and the result makes a fresh one (the
    registry's ``temp_bytes`` / ``alias_bytes`` of
    ``serving/paged_decode`` say so on the chip; ``chip_smoke.py``
    checks them)."""
    lengths = state["lengths"]
    active = state["active"]
    page_len = cache_page_len(pool)
    s_max = page_len * page_table.shape[1]
    idx_w = jnp.minimum(lengths, s_max - 1)
    p_ = param_transform(params) if param_transform is not None else params
    # a model with recurrent state (inference/cache.py): each slot's
    # rides the "cache" collection, and a row that does not decode keeps
    # its own (the module is handed ``active``)
    stateful = len(state_units(pool)) > 0
    if use_kernel:
        # the kernel walks every page of a row's length, so a row that
        # does not decode (released, or waiting for its prefill chunks:
        # it keeps its old length) is handed 0 and attends its own token
        # only; its output was thrown away below already. Positions, the
        # append and the new lengths keep idx_w.
        view = make_paged_view(pool, page_table, jnp.where(active, idx_w, 0))
        if stateful:
            view["cache"] = slot_state_view(view["cache"])
        logits, vars_out, counts = apply_decode(
            module, {"params": p_, **view}, state["last_token"][:, None],
            idx_w[:, None], lambda: active[:, None], ["cache", "kv_token"])
        tok = vars_out.get("kv_token")
        if tok is None or len(jax.tree.leaves(tok)) == 0:
            raise ValueError(
                "paged-attention kernel decode requires the module to "
                "publish the 'kv_token' collection (models/layers.py "
                "SelfAttention does) — there is no contiguous view to "
                "re-slice the token's K/V from")
    else:
        cache = gather_pages(pool, page_table, dequant_dtype=dequant_dtype)
        cache = set_cache_index(cache, idx_w)
        if stateful:
            cache = slot_state_view(cache)
        logits, vars_out, counts = apply_decode(
            module, {"params": p_, "cache": cache},
            state["last_token"][:, None], idx_w[:, None],
            lambda: active[:, None], ["cache", "kv_token"])
        tok = _token_tree(vars_out, vars_out["cache"], idx_w)
    nxt = _sample_impl(logits[:, -1, :], jax.random.fold_in(rng, it),
                       t, k, p, greedy, has_k, has_p)

    page_idx = idx_w // page_len
    phys = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]
    phys = jnp.where(active, phys, NULL_PAGE)
    pool = scatter_token_pages(pool, tok, phys, idx_w % page_len)
    if stateful:
        pool = store_decode_state(pool, vars_out["cache"])

    remaining = jnp.where(active, state["remaining"] - 1, state["remaining"])
    done = active & ((nxt == eos_id) | (remaining <= 0))
    new_state = {
        "lengths": jnp.where(active, lengths + 1, lengths),
        "last_token": jnp.where(active, nxt, state["last_token"]),
        "active": active & ~done,
        "remaining": remaining,
    }
    out_tok = jnp.where(active, nxt, -1)
    return pool, new_state, out_tok, done, counts


_paged_decode_jit = track_program(
    "serving/paged_decode",
    jax.jit(_paged_decode_iter_impl,
            static_argnums=(0, 11, 12, 13, 14, 15, 16),
            donate_argnums=(2, 4)), subsystem="serving")


def _chunk_prefill_impl(module, params, pool, state, ptab_row, chunk_ids,
                        chunk_start, end_pos, slot, max_new, is_last, rng,
                        eos_id, t, k, p, param_transform, greedy, has_k,
                        has_p, dequant_dtype=None, restore=None,
                        snap_run=None):
    """Prefill one page-aligned chunk of one request through its slot's
    gathered row view and scatter the chunk's K/V into its pages.
    ``restore`` and ``snap_run`` are a snapshot pool's (inference/
    cache.py): the entry this chunk starts from (negative: none) and the
    entry each of its pages' end states goes to; None for every other
    model, whose program has no such arguments.

    ``chunk_ids`` is ``[1, chunk]`` (right-padded to a page multiple,
    ``chunk_start`` page-aligned, ``chunk_start + chunk <= cache_len``
    by construction — see PagingConfig.validate). Earlier chunks and any
    shared prefix pages are already in the pool, so the dense cache path
    attends over them exactly as a whole-prompt prefill would. The first
    token is sampled every call but only published when ``is_last`` —
    one compiled program per chunk bucket, mid/last selected by a traced
    flag, not a specialization."""
    row = gather_pages(pool, ptab_row[None], scalar_index=True,
                       dequant_dtype=dequant_dtype)
    row = set_cache_index(row, chunk_start)
    page_len = cache_page_len(pool)
    stateful = len(state_units(pool)) > 0
    if stateful:
        # the chunk starts on a page boundary, from the state at the end
        # of the page before it: an earlier chunk's, or a shared page's
        # (a prefix hit restores the state here, with the K/V)
        first = chunk_start // page_len
        row = chunk_state_view(row, pool, ptab_row[jnp.maximum(first - 1, 0)],
                               first == 0, slot, restore)
    positions = chunk_start + jnp.arange(chunk_ids.shape[1])
    p_ = param_transform(params) if param_transform is not None else params

    def last_index():
        return jnp.clip(end_pos - 1 - chunk_start, 0, chunk_ids.shape[1] - 1)
    # a module whose upper layers keep nothing (``splits_positions``,
    # models/phi4flash.py) runs them on the one position whose logits are
    # read below, not on the chunk: its logits come back ``[1, 1, vocab]``
    splits = getattr(type(module), "splits_positions", False)
    needed = {"positions_needed": last_index()[None]} if splits else {}
    # the chunk's right padding is no token: an expert layer routes it
    # nowhere
    logits, vars_out, counts = apply_decode(
        module, {"params": p_, "cache": row}, chunk_ids, positions,
        lambda: (positions < end_pos)[None], ["cache", "kv_token"],
        **needed)

    chunk = chunk_ids.shape[1]
    tok_tree = vars_out.get("kv_token")
    if tok_tree is None or len(jax.tree.leaves(tok_tree)) == 0:
        tok_tree = _chunk_tree_from_cache(vars_out["cache"], chunk_start,
                                          chunk)
    run = jax.lax.dynamic_slice(ptab_row, (chunk_start // page_len,),
                                (chunk // page_len,))
    pool = scatter_chunk_pages(pool, tok_tree, run)
    if stateful:
        pool = store_chunk_state(pool, vars_out["cache"], tok_tree, slot, run,
                                 snap_run)

    last = (logits if splits else jax.lax.dynamic_slice_in_dim(
        logits, last_index(), 1, axis=1))[:, 0]                # [1, vocab]
    tok = _sample_impl(last, rng, t, k, p, greedy, has_k, has_p)[0]
    remaining = max_new - 1
    done = (tok == eos_id) | (remaining <= 0)

    def sel(new, old):
        return jnp.where(is_last, new, old)

    state = {
        "lengths": state["lengths"].at[slot].set(
            sel(end_pos, state["lengths"][slot])),
        "last_token": state["last_token"].at[slot].set(
            sel(tok, state["last_token"][slot])),
        "active": state["active"].at[slot].set(
            sel(~done, state["active"][slot])),
        "remaining": state["remaining"].at[slot].set(
            sel(remaining, state["remaining"][slot])),
    }
    return pool, state, tok, done, counts


CHUNK_PREFILL_STATICS = (0, 16, 17, 18, 19, 20)
_chunk_prefill_jit = track_program(
    "serving/chunk_prefill",
    jax.jit(_chunk_prefill_impl, static_argnums=CHUNK_PREFILL_STATICS,
            donate_argnums=(2, 3)), subsystem="serving")


class ProgramResult(NamedTuple):
    """What a served program hands the engine, named here on the host:
    the impls return plain tuples (a ``NamedTuple`` out of a jit changes
    the lowered text), and the pool stays with the manager."""
    state: Any
    tokens: Any                 # -1 where the row emitted none
    done: Any
    counts: Any                 # an expert layer's router counts, or None
    state_pages: int = 0        # a chunk's whole pages, each stored with
                                # the state at its end (a state a page)
    snapshot_table: Any = None  # a chunk's: the snapshot pool's table
    cross_positions: Optional[int] = None   # a chunk's: the positions its
                                # module's upper layers ran on, where they
                                # keep nothing and run on fewer than the
                                # chunk (``splits_positions``)


class Admission(NamedTuple):
    """What ``try_admit`` reserved, as admission counts it."""
    shared_tokens: int
    state_restored: Optional[bool]  # the first chunk starts from a stored
                                    # state (None: the model keeps none)
    state_missed: bool          # a hit shortened for want of a snapshot


def _sampling(eos, mode, param_transform):
    """The arguments every impl ends on, in its order (``mode`` is
    ``generation._sampling_mode``'s tuple)."""
    greedy, has_k, has_p, t, k, p = mode
    return eos, t, k, p, param_transform, greedy, has_k, has_p


class PagedKVManager:
    """Host-side owner of the pool, the allocator, the prefix cache and
    the per-slot page tables, and the one caller of the programs above:
    the engine hands over what only it knows and reads a
    ``ProgramResult``; what kinds of state a model keeps is known here.
    It never forces a device sync (page-table updates are async
    ``.at[].set`` dispatches, stamped with trace spans)."""

    def __init__(self, module, params, config):
        pcfg = config.paging
        self.config = pcfg
        self.page_len = pcfg.page_len
        self.cache_len = config.cache_len
        self.max_pages = config.cache_len // self.page_len
        self.num_pages = pcfg.pool_pages(config.num_slots, config.cache_len)
        self._module = module          # kept for reset() (fault recovery)
        self._params = params
        self._num_slots = config.num_slots
        self.kv_quant = "int8" if config.kv_int8 else None
        self.use_kernel = self._resolve_kernel(pcfg.kernel)
        self.chunk_programs = {}       # chunk tokens -> its executable
                                       # (shapes outlive reset())
        self.reset()
        log_dist(
            f"paged KV: {self.num_pages - 1} usable pages x "
            f"{self.page_len} tokens "
            f"(= {(self.num_pages - 1) * self.page_len // self.cache_len} "
            f"full-length rows), prefill chunk "
            f"{pcfg.prefill_chunk or 'chosen per dispatch'}, "
            f"prefix cache "
            f"{'on' if self.prefix is not None else 'off'}, decode "
            f"{'paged-attention kernel' if self.use_kernel else 'gather'}"
            f"{', int8 KV pages' if self.kv_quant else ''}", ranks=[0])

    def _resolve_kernel(self, mode: str) -> bool:
        """Resolve the ``serving.paging.kernel`` knob: "on" forces the
        paged-attention kernel (interpret mode runs it anywhere; real
        TPU needs a 128-aligned page_len — refused loudly, never a
        silent gather), "off" forces the PR-6 gather path (bitwise
        identical to the pre-kernel engine), "auto" turns the kernel on
        exactly where it is the proven win: real TPU with an aligned
        page_len. CPU runs stay on the gather path by default so
        replay/bit-reproducibility contracts hold."""
        from ...ops.pallas import tuning
        from ...ops.pallas._common import interpret_mode, log_fallback_on_tpu
        aligned = self.page_len % 128 == 0
        if mode == "on":
            if not (aligned or interpret_mode()):
                raise ValueError(
                    f"serving.paging.kernel='on' needs page_len % 128 == "
                    f"0 on TPU (got {self.page_len})")
            use, reason = True, None
        elif mode == "off":
            use, reason = False, "serving.paging.kernel='off'"
        elif interpret_mode():
            use, reason = False, "platform is not tpu"
        else:
            use = aligned
            reason = (None if aligned else
                      f"page_len {self.page_len} not a multiple of 128")
        # the engine-level choice (kernel vs gather-then-contiguous-
        # decode), visible next to the kernel-level records
        tuning.record_dispatch(
            "paged_decode", "path", f"page{self.page_len}", mode,
            impl="kernel" if use else "gather", reason=reason)
        if not use:
            log_fallback_on_tpu("serving paged decode", "gather", reason)
        return use

    def _build_pool(self):
        """Fresh zeroed pool; ``dequant_dtype`` records the model's KV
        compute dtype BEFORE int8 conversion — gathers dequantize back
        to it, so the gathered view always matches what the attention
        path writes into it."""
        # a model with recurrent state is known by its cache collection:
        # its pool also keeps each slot's state, and the states at page
        # ends one a page or in a snapshot pool (inference/cache.py)
        entries = self.config.snapshot_entries(self._num_slots)
        pool = init_page_pool(self._module, self._params, self.num_pages,
                              self.page_len, self._num_slots, entries)
        self.dequant_dtype = kv_leaves(pool)[0].dtype
        # a latent-attention model is known by its cache collection too:
        # a unit of keys with no values beside them
        self.has_latent = has_latent_units(pool)
        if self.kv_quant:
            self.refuse_latent("serving.kv_int8 (quantize_page_pool: int8 "
                               "pages)")
            pool = quantize_page_pool(pool)
        self.has_state = has_recurrent_state(pool)
        # window layers' rings (inference/cache.py): kept a slot, with
        # nothing at a page's end for a prefix hit to start from
        self.has_rings = has_ring_units(pool)
        if self.has_rings:
            self.window = next(u["ring_key"].shape[-1]
                               for u in state_units(pool) if "ring_key" in u)
            self.refuse_rings(pool, self.config.enable_prefix_cache,
                              "serving.paging.enable_prefix_cache",
                              "a prefix hit would start from")
            self.refuse_rings(pool, self.kv_quant, "serving.kv_int8",
                              "int8 pages would quantize")
            widest = self.config.prefill_chunk or self.page_len * max(
                w for w in CHUNK_PAGES if w <= self.max_pages)
            if widest > self.window:
                raise NotImplementedError(
                    f"a prefill chunk of {widest} tokens over "
                    f"{type(self._module).__name__}'s window of "
                    f"{self.window}: a chunk writes each ring lane once, "
                    "so it is at most the window (serving.paging."
                    "prefill_chunk, or page_len x "
                    f"{max(CHUNK_PAGES)} where it is unset)")
        self.page_states = has_page_states(pool)
        self.snapshots = (SnapshotTable(entries)
                          if has_snapshot_pool(pool) else None)
        self._state_bytes = state_bytes(pool)      # shapes: read once
        # the scatter/gather/kernel paths all key off the scale planes
        # structurally — assert the built pool agrees with the config
        # so a layout drift fails HERE, not as silent fp math
        assert pool_is_quantized(pool) == bool(self.kv_quant)
        return pool

    # -- admission ---------------------------------------------------------
    def pages_for(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages for a request's full token budget."""
        return -(-(prompt_len + max_new) // self.page_len)

    def try_admit(self, slot: int, prompt: np.ndarray, max_new: int):
        """Allocate (and prefix-match) pages for one request. Returns its
        ``Admission`` on success, or None when the pool cannot
        cover the request even after prefix-cache eviction — the caller
        leaves the request queued (admission gates on free pages)."""
        prompt_len = int(prompt.shape[0])
        shared: List[int] = []
        branch = None
        if self.prefix is not None:
            shared = self.prefix.match(prompt)
            if shared and self.snapshots is not None:
                # a hit starts from a stored state: it goes as deep as
                # the deepest matched page whose end has a snapshot, and
                # where that is short of the match this request takes
                # the snapshot the match wanted as it passes (the branch:
                # paging/snapshots.py)
                depth = next((d for d in range(len(shared), 0, -1)
                              if self.snapshots.has(shared[d - 1])), 0)
                if depth < len(shared):
                    branch = (len(shared) - 1, shared[-1])
                    shared = shared[:depth]
            if shared:
                # pin the matched run BEFORE any eviction below: once
                # deeper leaves are gone the matched nodes themselves
                # become evictable, and an unpinned page could be freed
                # and re-handed out as a private page — aliased twice in
                # this slot's table, or a crash on the late retain
                self.allocator.retain(shared)
        need = self.pages_for(prompt_len, max_new) - len(shared)
        private = self.allocator.alloc(need)
        if private is None and self.prefix is not None:
            self.prefix.evict(need)
            private = self.allocator.alloc(need)
        if private is None:
            if shared:
                self.allocator.release(shared)
            return None
        if self.prefix is not None:
            self.prefix.note_admitted(len(shared))
        pages = shared + private
        self._slot_pages[slot] = pages
        if self.snapshots is not None:
            self._plan_snapshots(slot, prompt_len, shared, branch)
        row = np.full((self.max_pages,), NULL_PAGE, np.int32)
        row[:len(pages)] = pages
        with _span("serving/page_table_copy", {"slot": slot,
                                               "pages": len(pages)}):
            self.page_table = self.page_table.at[slot].set(row)
        restored = None
        if self.has_state:
            # the first chunk's program reads the stored state by its
            # page (``chunk_state_view``): the host names it and counts
            args = {"slot": slot, "page": shared[-1] if shared else None}
            with _span("serving/state_restore", args):
                restored = bool(shared)
        plan = self._slot_snapshots[slot]
        return Admission(len(shared) * self.page_len, restored,
                         bool(plan and plan["missed"]))

    def _plan_snapshots(self, slot, prompt_len, shared, branch):
        """What the slot's chunks will do with the snapshot pool: the
        entry its first chunk starts from (pinned until that chunk is
        dispatched), and the page ends it will store — ``{page index of
        the prompt: physical page the entry is kept under}``. The branch
        is kept under the prefix cache's page (retained here until it is
        stored, so the id cannot change hands); the leaf under the
        slot's own page, which ``publish`` then shares."""
        restore = shared[-1] if shared else None
        if restore is not None:
            self.snapshots.pin(restore)
        wanted = {}
        if branch is not None:
            self.allocator.retain([branch[1]])
            wanted[branch[0]] = branch[1]
        leaf = prompt_len // self.page_len - 1
        if leaf >= len(shared) and leaf not in wanted:
            wanted[leaf] = self._slot_pages[slot][leaf]
        self._slot_snapshots[slot] = {
            "restore": restore, "wanted": wanted, "missed": branch is not None,
            "held": None if branch is None else branch[1]}

    def _chunk_snapshots(self, slot: int, start: int, pages: int):
        """The snapshot arguments of the slot's chunk program over
        ``pages`` pages from token ``start``: ``(restore, snap_run)`` —
        the entry it starts from (-1: its slot's own state, or zeros at
        position 0) and the entry each page's end state goes to
        (``NULL_SNAPSHOT``: none wanted, or every entry pinned) — or
        ``()`` for a model without a snapshot pool, whose program has no
        such arguments. Called as the chunk is dispatched: the entries
        are the table's from here."""
        if self.snapshots is None:
            return ()
        plan = self._slot_snapshots[slot]
        restore = -1
        if plan["restore"] is not None:
            restore = self.snapshots.lookup(plan["restore"])
            self.snapshots.unpin(plan["restore"])
            plan["restore"] = None
        first = start // self.page_len
        run = np.full((pages,), NULL_SNAPSHOT, np.int32)
        for i in range(pages):
            page = plan["wanted"].pop(first + i, None)
            if page is None:
                continue
            entry = self.snapshots.take(page)
            if entry is not None:
                run[i] = entry
            if page == plan["held"]:
                plan["held"] = None
                self.allocator.release([page])
        return jnp.int32(restore), jnp.asarray(run)

    def publish(self, slot: int, prompt: np.ndarray) -> int:
        """Insert the prompt's full pages into the prefix cache once its
        prefill completed (pages are immutable from here: decode appends
        strictly past the prompt's full-page region)."""
        if self.prefix is None:
            return 0
        pages = self._slot_pages[slot]
        n_full = int(prompt.shape[0]) // self.page_len
        return self.prefix.insert(prompt, pages[:n_full])

    def release_slot(self, slot: int):
        """Return a finished/cancelled slot's page references and null
        its table row (stale entries must not alias pages a future owner
        allocates)."""
        pages = self._slot_pages[slot]
        if pages is None:
            return
        self._slot_pages[slot] = None
        plan = self._slot_snapshots[slot]
        if plan is not None:            # released before its chunks ran
            self._slot_snapshots[slot] = None
            if plan["restore"] is not None:
                self.snapshots.unpin(plan["restore"])
            if plan["held"] is not None:
                self.allocator.release([plan["held"]])
        self.allocator.release(pages)
        with _span("serving/page_table_copy", {"slot": slot, "pages": 0}):
            self.page_table = self.page_table.at[slot].set(
                jnp.full((self.max_pages,), NULL_PAGE, jnp.int32))

    # -- the served programs: each argument list is spelled here, once ----
    def decode(self, module, params, state, rng, iteration, eos, mode,
               param_transform) -> ProgramResult:
        """One ``serving/paged_decode`` step over the slot batch."""
        self.pool, *out = _paged_decode_jit(
            module, params, self.pool, self.page_table, state, rng,
            jnp.int32(iteration), *_sampling(eos, mode, param_transform),
            self.use_kernel, self.dequant_dtype)
        return ProgramResult(*out)

    def spec_verify(self, module, params, state, proposals, counts, rng,
                    iteration, eos, mode, param_transform) -> ProgramResult:
        """One ``serving/spec_verify_iter`` step (serving/speculation.py,
        which imports this package: hence imported here)."""
        from ..speculation import _spec_verify_jit
        self.pool, *out = _spec_verify_jit(
            module, params, self.pool, self.page_table, state,
            jnp.asarray(proposals), jnp.asarray(counts), rng,
            jnp.int32(iteration), *_sampling(eos, mode, param_transform),
            self.dequant_dtype)
        return ProgramResult(*out, counts=None)

    def _chunk_program(self, snaps, module, params, state, slot, chunk_ids,
                       start, end_pos, max_new, is_last, rng, eos, mode,
                       param_transform):
        """``(executable, arguments)`` of ``serving/chunk_prefill`` at
        the width of ``chunk_ids``: the one road to a chunk program. A
        width not in the table yet is compiled from these very arguments
        (nothing runs, nothing is donated) and kept."""
        args = (module, params, self.pool, state, self.page_table[slot],
                jnp.asarray(chunk_ids), jnp.int32(start), jnp.int32(end_pos),
                jnp.int32(slot), jnp.int32(max_new), jnp.asarray(is_last),
                rng, *_sampling(eos, mode, param_transform),
                self.dequant_dtype, *snaps)
        width = chunk_ids.shape[1]
        if width not in self.chunk_programs:
            self.chunk_programs[width] = _chunk_prefill_jit.compile_ahead(
                *args, static_argnums=CHUNK_PREFILL_STATICS)
        return self.chunk_programs[width], args

    def compile_chunks(self, module, params, state, rng, eos, mode,
                       param_transform):
        """Compile the chunk program at every width ``chunk_pages`` may
        choose, from shapes. A fixed ``prefill_chunk`` leaves nothing to
        choose: its widths compile at their first dispatch."""
        if self.config.prefill_chunk is not None:
            return
        for pages in (w for w in CHUNK_PAGES if w <= self.max_pages):
            snaps = (() if self.snapshots is None else
                     (jnp.int32(-1), jnp.zeros((pages,), jnp.int32)))
            self._chunk_program(
                snaps, module, params, state, 0,
                np.zeros((1, pages * self.page_len), np.int32), 0, 0, 0,
                False, rng, eos, mode, param_transform)

    def prefill_chunk(self, module, params, state, slot, chunk_ids, start,
                      end_pos, max_new, is_last, rng, eos, mode,
                      param_transform) -> ProgramResult:
        """One ``serving/chunk_prefill`` dispatch: ``chunk_ids`` (``[1,
        width]``, right-padded) of the slot's prompt of ``end_pos``
        tokens, from token ``start``."""
        width = chunk_ids.shape[1]
        program, args = self._chunk_program(
            self._chunk_snapshots(slot, start, width // self.page_len),
            module, params, state, slot, chunk_ids, start, end_pos, max_new,
            is_last, rng, eos, mode, param_transform)
        self.pool, *out = program(*args)
        stored = 0
        if self.page_states:
            stored = (min(start + width, end_pos) - start) // self.page_len
        split = getattr(type(module), "splits_positions", False)
        return ProgramResult(*out, state_pages=stored,
                             snapshot_table=self.snapshots,
                             cross_positions=1 if split else None)

    def decode_walked(self, tokens, requests):
        """``(rows, latent tokens, ring walk)`` the decode dispatch just
        read back walked (``requests``: its slot -> request), each None
        where this pool has no such count: the rows that kept a token,
        which alone were handed a length, when the paged kernel runs;
        over a latent pool the tokens those rows attended — a row's
        prompt and all it had generated but the token it was fed (ask
        before they emit); over a pool with window rings ``(pooled
        tokens, ring tokens, rows)``: what a call over the shared pages
        read (the same contexts), what a call over a ring read (each
        context and the token itself, at most the window) and the
        columns a ring was written."""
        rows = np.count_nonzero(tokens >= 0) if self.use_kernel else None
        if not (self.has_latent or self.has_rings):
            return rows, None, None
        contexts = [req.prompt.shape[0] + len(req.output_tokens) - 1
                    for slot, req in enumerate(requests)
                    if req is not None and not req.done and tokens[slot] >= 0]
        if self.has_latent:
            return rows, sum(contexts), None
        return rows, None, (sum(contexts),
                            sum(min(c + 1, self.window) for c in contexts),
                            len(contexts))

    # -- page-granular handoff (serving/fleet disaggregation) --------------
    def export_slot(self, slot: int, prefill_len: int):
        """Read the slot's prefilled page CONTENTS out of the pool for a
        cross-replica handoff: only pages below the prefill frontier
        travel (``ceil(prefill_len / page_len)`` — decode appends
        strictly past them on the receiver, so the still-unwritten
        budget pages are garbage nobody copies). Returns
        ``(unit_records, n_filled)``; the caller owns releasing the slot
        once the payload is safely handed off."""
        self.refuse_state("a page handoff (export_slot)")
        self.refuse_latent("a page handoff (fleet/handoff.py export_slot: "
                           "export_pages)")
        pages = self._slot_pages[slot]
        if pages is None:
            raise ValueError(f"export of unowned slot {slot}")
        n_filled = -(-int(prefill_len) // self.page_len)
        page_ids = pages[:n_filled]
        with _span("serving/handoff_export", {"slot": slot,
                                              "pages": n_filled}):
            return export_pages(self.pool, page_ids), n_filled

    def import_slot(self, slot: int, kv_units, n_filled: int,
                    total_pages: int) -> bool:
        """Allocate ``total_pages`` fresh pages for an incoming handoff
        and write the ``n_filled`` transferred page records into the
        first of them (the same admission discipline as ``try_admit``:
        all-or-nothing, prefix-cache eviction as the fallback, False =
        page-starved — the caller retries on a later step). Shapes never
        change, so the receiver's compiled paged programs stay cached —
        the handoff is a page transfer, not a recompute."""
        self.refuse_state("a page handoff (import_slot)")
        self.refuse_latent("a page handoff (fleet/handoff.py import_slot: "
                           "import_pages)")
        if self._slot_pages[slot] is not None:
            raise ValueError(f"import into occupied slot {slot}")
        private = self.allocator.alloc(total_pages)
        if private is None and self.prefix is not None:
            self.prefix.evict(total_pages)
            private = self.allocator.alloc(total_pages)
        if private is None:
            return False
        with _span("serving/handoff_import", {"slot": slot,
                                              "pages": n_filled}):
            self.pool = import_pages(self.pool, private[:n_filled],
                                     kv_units)
            self._slot_pages[slot] = private
            row = np.full((self.max_pages,), NULL_PAGE, np.int32)
            row[:len(private)] = private
            self.page_table = self.page_table.at[slot].set(row)
        return True

    def refuse_state(self, what: str):
        """Pages alone are not such a model's state: what moves or
        re-runs them has to move or roll back the recurrent state too,
        and refuses until it does."""
        if self.has_state:
            raise NotImplementedError(
                f"{what} does not carry recurrent state: "
                f"{type(self._module).__name__} keeps "
                f"{describe_state(self.pool)} beside its K/V pages, which "
                "this path neither moves nor rolls back")

    def refuse_rings(self, pool, asked, option: str, why: str):
        """An option that a pool with window rings cannot serve, by
        name."""
        if asked:
            raise NotImplementedError(
                f"{option} is not built for {type(self._module).__name__}: "
                f"it keeps {describe_state(pool)} beside one "
                f"layer's K/V pages, and {why} what only the pages hold; "
                f"turn {option} off for this model")

    def refuse_latent(self, what: str):
        """What is written for pages of K and V heads is not built for a
        latent pool (one leaf a layer, keys 576 wide whose leading 512
        rows are the values), and says so by name."""
        if self.has_latent:
            raise ValueError(
                f"{what} is not built for a latent page pool: "
                f"{type(self._module).__name__} keeps one compressed "
                "vector a token and layer, not K and V heads, and this "
                "path's page geometry, scales or verification step "
                "assume the heads")

    def reset(self):
        """(Re)build the device pool and every host-side ownership structure
        from scratch — at construction, and on the fault-containment path
        (engine.recover): after a RESOURCE_EXHAUSTED mid-admit the donated
        pool buffers may be invalid, and after a requeue-and-re-prefill
        recovery every page's contents are stale anyway. Shapes are
        unchanged, so the compiled paged programs stay cached."""
        self.pool = self._build_pool()
        self.allocator = PageAllocator(
            self.num_pages,
            on_free=self.snapshots.drop if self.snapshots else None)
        self.prefix = (PrefixCache(self.page_len, self.allocator)
                       if self.config.enable_prefix_cache else None)
        self.page_table = jnp.full((self._num_slots, self.max_pages),
                                   NULL_PAGE, jnp.int32)
        self._slot_pages: List[Optional[List[int]]] = \
            [None] * self._num_slots
        self._slot_snapshots: List[Optional[dict]] = [None] * self._num_slots

    # -- accounting --------------------------------------------------------
    def pool_bytes(self) -> int:
        """Resident K/V bytes of the pool (all attention units)."""
        return sum(int(leaf.size) * leaf.dtype.itemsize
                   for leaf in kv_leaves(self.pool))

    def state_bytes(self) -> int:
        """Resident bytes of the recurrent state (0 for a model without)."""
        return self._state_bytes

    def account(self, acct, registry):
        """Tag the pool and the page tables in the HBM accountant
        (observability/memory.py) and set the pool's gauges, a model's
        state bytes in the serving metrics' ``registry`` (None: none is
        kept). Shape metadata only — no device reads."""
        acct.account("serving/kv_pool", num_bytes=self.pool_bytes(),
                     name="page_pool")
        acct.account("serving/kv_pool", self.page_table, name="page_table")
        acct.registry.gauge("mem/decode_gather_transient").set(
            self.decode_gather_transient_bytes())
        acct.registry.gauge("mem/kv_pool_resident").set(
            acct.subsystem_bytes("serving/kv_pool"))
        if self.has_state and registry is not None:
            registry.gauge("serving/state_bytes").set(self.state_bytes())

    def decode_gather_transient_bytes(self) -> int:
        """Bytes of the contiguous ``[num_slots, h, d, cache_len]`` view
        each jitted decode step of the GATHER path gathers as
        XLA-managed scratch — a hand count from the pool's own leaf
        shapes (the figure the PR-6 bench artifact computed;
        resident-vs-transient honesty in docs/serving.md), not a
        measurement. On the paged-attention KERNEL path no view is
        gathered and this returns 0; what that program really takes
        beside its arguments is the compiler's figure, ``temp_bytes``
        of ``serving/paged_decode`` in the program registry
        (``observability/programs.py``). On the gather path, per
        attention unit: one page's K/V elements (at the DEQUANT dtype —
        an int8 pool still gathers a full-precision view, so
        quantization does NOT shrink this figure, only the kernel
        eliminates it) times ``num_slots * max_pages``."""
        if self.use_kernel:
            return 0
        from jax.tree_util import tree_flatten_with_path
        num_slots = int(self.page_table.shape[0])
        itemsize = jnp.dtype(self.dequant_dtype).itemsize
        total = 0
        for path, leaf in tree_flatten_with_path(self.pool)[0]:
            name = getattr(path[-1], "key", None)
            if name in ("cached_key", "cached_value"):
                pages_dim = int(leaf.shape[leaf.ndim - 4])
                per_page = int(leaf.size) // pages_dim * itemsize
                total += per_page * num_slots * self.max_pages
        return total

    def stats(self) -> dict:
        usable = self.allocator.usable_pages
        out = {
            "pages_total": usable,
            "pages_in_use": self.allocator.pages_in_use,
            "page_utilization": self.allocator.pages_in_use / max(1, usable),
            "page_len": self.page_len,
            "pool_tokens": usable * self.page_len,
            "full_length_rows_equivalent":
                usable * self.page_len // self.cache_len,
            "kernel": self.use_kernel,
            "kv_quant": self.kv_quant,
        }
        if self.has_state:
            out["state_bytes"] = self.state_bytes()
        if self.snapshots is not None:
            out.update(state_snapshots=self.snapshots.entries,
                       state_snapshots_in_use=self.snapshots.in_use,
                       state_snapshots_taken=self.snapshots.taken,
                       state_snapshots_evicted=self.snapshots.evicted)
        if self.prefix is not None:
            out.update(self.prefix.stats())
            out["prefix_hit_rate"] = (self.prefix.hits
                                      / max(1, self.prefix.lookups))
        return out
