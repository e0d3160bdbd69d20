"""Token-exact self-speculative decoding (the ``serving.speculation``
block, docs/serving.md "Speculative decoding").

Decode emits one token per dispatch per slot while the hardware could
verify k tokens for nearly the price of one — the biggest remaining
per-request latency lever on repetitive traffic (the prefix-heavy
populations the radix cache already optimizes). This module closes it
WITHOUT a draft model and WITHOUT new compiled shapes per request:

- ``NgramProposer`` — a host-side prompt-lookup proposer on the
  deterministic step clock: match the tail n-gram of each slot's
  ``prompt + generated`` sequence against its own earlier history
  (longest n first, LAST occurrence wins) and propose the tokens that
  followed it. Pure numpy over token arrays the engine already holds —
  zero compiled programs, zero device syncs for proposal.
- ``_spec_verify_iter`` — ONE new compiled verification program
  (tracked via the program registry, compile-once asserted in
  tests/unit/test_speculation.py): a single batched multi-token decode
  step runs every slot's ``[last_token, p_1 .. p_K]`` block through the
  model at its own frontier (per-row cache_index, models/layers.py) and
  accepts the longest proposal prefix agreeing with greedy argmax. An
  accepted step emits ``accepted + 1`` tokens (the proposals plus the
  model's own next token — the standard speculative-decoding bonus),
  so the output is *bitwise identical* to the one-token-per-step
  engine: every emitted token IS the greedy argmax given its prefix.

Rollback is length-granular, alloc-free, and page-safe by
construction: the verification step writes all K+1 candidate K/V
entries at each slot's frontier, and acceptance simply decides how far
``lengths`` advances. Rejected entries sit PAST the new frontier —
exactly the prefill chunk's pad-tail convention — where the per-slot
length mask never reads them and later steps overwrite them in order.
Every write lands inside the slot's admission-time page budget (one
past it names the null page and is skipped), so speculation never
allocates, frees, or leaks a page and the allocator ``check()``
invariant holds after every rollback. Proposal-free iterations ride the existing
``serving/paged_decode`` program untouched.

Greedy-only by construction (config.validate refuses otherwise): the
acceptance rule IS greedy argmax — speculating under a sampling engine
would silently change the output distribution.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..inference.cache import (cache_page_len, extract_token_kv,
                               gather_pages, scatter_token_pages,
                               set_cache_index)
from ..inference.generation import _sample_impl
from ..observability.programs import track_program
from .paging.allocator import NULL_PAGE


class NgramProposer:
    """Draft-free prompt-lookup proposer (host numpy, deterministic).

    For a slot whose sequence is ``prompt + generated``, try suffix
    n-grams from ``ngram_max`` down to ``ngram_min``; on the first n
    with an earlier occurrence, propose up to ``k`` tokens that
    followed its LAST earlier occurrence (recent context beats stale
    context on self-similar traffic). Deterministic in the sequence
    alone — proposals replay bit-exactly on the engine's step clock.
    """

    def __init__(self, config):
        self.ngram_max = config.ngram_max
        self.ngram_min = config.ngram_min

    def propose(self, seq: np.ndarray, k: int) -> np.ndarray:
        """Up to ``k`` proposed continuation tokens for ``seq`` (int32,
        possibly empty — the engine masks empty slots out)."""
        seq = np.asarray(seq)
        n_seq = int(seq.shape[0])
        if k <= 0 or n_seq < self.ngram_min + 1:
            return np.zeros((0,), np.int32)
        for n in range(min(self.ngram_max, n_seq - 1),
                       self.ngram_min - 1, -1):
            suffix = seq[n_seq - n:]
            windows = np.lib.stride_tricks.sliding_window_view(seq, n)
            # [:-1] drops the suffix's own window: a match must END
            # strictly before the sequence tail so at least one
            # continuation token exists
            hits = np.flatnonzero((windows[:-1] == suffix).all(axis=1))
            if hits.size:
                start = int(hits[-1]) + n
                return np.asarray(seq[start:start + k], np.int32)
        return np.zeros((0,), np.int32)


def _spec_verify_impl(module, params, pool, page_table, state, proposals,
                      counts, rng, it, eos_id, t, k, p, param_transform,
                      greedy, has_k, has_p, dequant_dtype=None):
    """One batched speculative verification step over the full slot
    batch — the multi-token sibling of
    ``paging.manager._paged_decode_iter_impl``, and the ONLY program
    speculation adds; it compiles exactly once per engine shape.

    ``proposals`` is ``[slots, K]`` int32 (K = ``max_spec_tokens``, a
    fixed shape — the QoS budget shrinks ``counts``, never the shape),
    ``counts`` the per-slot valid-proposal count (0 = slot rides along
    masked).

    Per slot: gather the contiguous view (the kernel path is
    single-token-only — verification always gathers), run
    ``[last_token, p_1 .. p_K]`` through one decode step at the slot's
    own frontier (per-row multi-token cache_index path; the cache
    headroom — ``config.cache_len`` pads ``max_len`` by
    ``max_spec_tokens`` — guarantees an ACTIVE slot's K+1-token window
    never clamps), take the greedy argmax chain ``nxt``, accept the
    longest proposal prefix matching it, and emit ``e = min(accepted +
    1, first eos, remaining budget)`` tokens. The K+1 K/V entries are
    scattered back position by position, one append each, as short as
    the rows it writes; writes past a slot's allocated budget hit
    NULL_PAGE table entries, which the append skips as it skips an
    inactive slot's, so speculation never touches a page it doesn't
    own. Rejected candidate K/V stays past the advanced frontier
    (garbage by the prefill chunk's pad-tail convention) — the rollback
    is "don't advance ``lengths``", never an alloc or free.
    """
    lengths = state["lengths"]
    active = state["active"]
    n_slots, n_prop = proposals.shape
    s = n_prop + 1
    inp = jnp.concatenate([state["last_token"][:, None], proposals], axis=1)

    p_ = param_transform(params) if param_transform is not None else params
    page_len = cache_page_len(pool)
    s_max = page_len * page_table.shape[1]
    idx_w = jnp.minimum(lengths, s_max - s)
    cache = gather_pages(pool, page_table, dequant_dtype=dequant_dtype)
    cache = set_cache_index(cache, idx_w)
    positions = idx_w[:, None] + jnp.arange(s)[None, :]
    logits, vars_out = module.apply(
        {"params": p_, "cache": cache}, inp, decode=True,
        positions=positions, mutable=["cache", "kv_token"])
    tok = vars_out.get("kv_token")
    has_tok = tok is not None and len(jax.tree.leaves(tok)) > 0
    for i in range(s):
        if has_tok:
            tok_i = jax.tree.map(
                lambda leaf: jax.lax.slice_in_dim(
                    leaf, i, i + 1, axis=-1), tok)
        else:
            tok_i = extract_token_kv(vars_out["cache"], idx_w + i)
        pos = idx_w + i
        phys = jnp.take_along_axis(page_table, (pos // page_len)[:, None],
                                   axis=1)[:, 0]
        phys = jnp.where(active, phys, NULL_PAGE)
        pool = scatter_token_pages(pool, tok_i, phys, pos % page_len)

    # greedy chain: nxt[:, i] is the argmax given last_token + the first
    # i proposals — when those proposals all match the chain, it IS the
    # token the sequential engine would have emitted at step i
    nxt = _sample_impl(logits.reshape(n_slots * s, -1),
                       jax.random.fold_in(rng, it),
                       t, k, p, greedy, has_k, has_p).reshape(n_slots, s)

    valid = jnp.arange(n_prop)[None, :] < counts[:, None]
    match = (proposals == nxt[:, :n_prop]) & valid
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    # emitted count e: accepted proposals + the bonus token, cut at the
    # first emitted eos and at the remaining budget — exactly where the
    # sequential one-token loop would have stopped
    pos_s = jnp.arange(s)[None, :]
    emit_cap = acc + 1
    eos_pos = jnp.min(jnp.where((nxt == eos_id) & (pos_s < emit_cap[:, None]),
                                pos_s, s), axis=1)
    e = jnp.minimum(emit_cap, jnp.minimum(eos_pos + 1, state["remaining"]))
    e = jnp.where(active, e, 0)

    remaining = jnp.where(active, state["remaining"] - e, state["remaining"])
    done = active & (((eos_pos + 1) <= e) | (remaining <= 0))
    new_state = {
        "lengths": jnp.where(active, lengths + e, lengths),
        "last_token": jnp.where(
            active, nxt[jnp.arange(n_slots), jnp.maximum(e - 1, 0)],
            state["last_token"]),
        "active": active & ~done,
        "remaining": remaining,
    }
    out_toks = jnp.where(active[:, None] & (pos_s < e[:, None]), nxt, -1)
    return pool, new_state, out_toks, done


_spec_verify_jit = track_program(
    "serving/spec_verify_iter",
    jax.jit(_spec_verify_impl, static_argnums=(0, 13, 14, 15, 16, 17),
            donate_argnums=(2, 4)), subsystem="serving")
