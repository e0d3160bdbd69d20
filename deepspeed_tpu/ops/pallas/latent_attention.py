"""Pallas paged decode attention over a LATENT page pool (the absorbed
form of multi-head latent attention, DeepSeek-V2/V3).

A latent pool keeps one vector a token and layer, ``[num_pages, 1, D,
page_len]`` in the K^T layout of ``paged_attention``'s pools: the
normalised compressed vector (``value_width`` rows, ``kv_lora_rank``)
followed by the rotated key part every head shares (``D - value_width``
rows). With the key up-projection folded into the query and the value
up-projection applied after the softmax, every query head of a row reads
the same ``[D, tokens]`` block:

    score[h, t] = q[h, :] . latent[:, t]                  (all D rows)
    out[h, :]   = sum_t softmax(score)[h, t] latent[:value_width, t]

so the grid is the rows alone and a step multiplies ALL heads against a
block at once — one ``[H, D] x [D, tokens]`` product and one ``[H, tokens]
x [tokens, value_width]`` product a block, no head's rows ever sliced
out of the query (``paged_attention`` slices a K/V head's group of rows,
and where its products are float32 a step is held to eight rows: the
one-row cut of a boolean mask that Mosaic aborts on, its ``MAX_ROWS``).
Keys and values are one buffer: a page is fetched once and its leading
rows are the values.

The walk is ``paged_attention``'s: the row's pages stream HBM -> VMEM in
place through double-buffered DMAs, the physical page from the
scalar-prefetched table, the layer-stacked pool indexed and never
sliced, a row of length 0 walks nothing, and the current token (not in
the pool yet) is folded in as one always-valid column. Inference only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tuning
from ._common import NEG_INF
from ._common import interpret_mode as _interpret
from ._common import log_fallback_on_tpu

DEFAULT_BLOCK_TOKENS = 512

KERNEL = "latent_attention"


def _kernel(len_ref, ptab_ref, layer_ref, q_ref, new_ref, newv_ref, pool_hbm,
            o_ref, buf0, buf1, sem, m_ref, l_ref, acc_ref, *, scale,
            page_len, ppb, max_pages, vw):
    b = pl.program_id(0)
    length = len_ref[b]
    layer = layer_ref[0]
    bufs = (buf0, buf1)
    bt = ppb * page_len
    nb = pl.cdiv(length, bt)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # the products run in the pool's own type: a bf16 pool takes one
    # pass of the MXU, a float32 pool what the ambient matmul precision
    # asks of a float32 product
    cdt = buf0.dtype
    q = (q_ref[0].astype(jnp.float32) * scale)               # [H, D]

    def copies(j, slot):
        # logical pages past the table clamp to its last entry, a valid
        # physical page whose columns the length mask discards
        descs = []
        for i in range(ppb):
            logical = jnp.minimum(j * ppb + i, max_pages - 1)
            phys = ptab_ref[b, logical]
            descs.append(pltpu.make_async_copy(
                pool_hbm.at[layer, phys, 0],
                bufs[slot].at[:, pl.ds(i * page_len, page_len)],
                sem.at[slot, i]))
        return descs

    # no copy is started that a zero-block row would never wait for
    # (paged_attention has the hazard spelled out)
    @pl.when(nb > 0)
    def _first_copies():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)

        for parity in (0, 1):
            @pl.when((slot == parity) & (j + 1 < nb))
            def _prefetch():
                for c in copies(j + 1, 1 - parity):
                    c.start()

        for parity in (0, 1):
            @pl.when(slot == parity)
            def _compute():
                for c in copies(j, parity):
                    c.wait()
                lat = bufs[parity][...]                       # [D, bt]
                s = jnp.dot(q.astype(cdt), lat,
                            preferred_element_type=jnp.float32)  # [H, bt]
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                    + j * bt
                valid = col < length
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[...]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)                        # [H, bt]
                # columns past the row's length may hold another
                # request's garbage: 0 x NaN = NaN, so they are zeroed
                # (masked as float32: Mosaic lays no one-row mask over
                # a bf16 tile)
                val = jnp.where(valid[:1],
                                bufs[parity][:vw, :].astype(jnp.float32),
                                0.0).astype(cdt)              # [vw, bt]
                pv = jax.lax.dot_general(
                    p.astype(cdt), val, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [H, vw]
                l_ref[...] = corr * l_ref[...] \
                    + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[...] = corr * acc_ref[...] + pv
                m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, nb, body, 0)
    # the current token: one always-valid column, so l > 0 for every row
    s = jnp.sum(q * new_ref[0].astype(jnp.float32), axis=-1, keepdims=True)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                    # [H, 1]
    l = corr * l_ref[...] + p
    acc = corr * acc_ref[...] + p * newv_ref[0].astype(jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _latent_dma(q, pool, ptab, lengths, new, layer, *, scale, page_len, ppb,
                vw):
    b, heads, d = q.shape
    max_pages = ptab.shape[1]
    bt = ppb * page_len
    row = lambda width: pl.BlockSpec((1, 1, width),
                                     lambda bi, *_: (bi, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_len=page_len, ppb=ppb,
                          max_pages=max_pages, vw=vw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, heads, d), lambda bi, *_: (bi, 0, 0)),
                      row(d), row(vw),
                      # the whole stacked pool stays in HBM
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, vw),
                                   lambda bi, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((d, bt), pool.dtype),
                pltpu.VMEM((d, bt), pool.dtype),
                pltpu.SemaphoreType.DMA((2, ppb)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, vw), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, vw), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        # its own name in the device trace: no pattern for the paged
        # kernel's ``%attn.N`` catches it
        name="latent_attn",
    )(lengths, ptab, layer, q, new[:, None, :], new[:, None, :vw], pool)


def _latent_dense(q, pool, ptab, lengths, new, layer, *, scale, vw):
    """jnp twin with the same semantics: what the kernel is held to, and
    the fallback for a page length Mosaic cannot tile."""
    b, heads, d = q.shape
    page_len = pool.shape[-1]
    g = pool[layer[0], ptab][:, :, 0]                  # [B, M, D, p]
    lat = g.transpose(0, 2, 1, 3).reshape(b, d, -1).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bhd,bdt->bht", qf, lat)
    col = jnp.arange(lat.shape[-1])[None, None, :]
    ln = lengths[:, None, None]
    s = jnp.where(col < ln, s, NEG_INF)
    s_new = jnp.einsum("bhd,bd->bh", qf, new.astype(jnp.float32))[..., None]
    probs = jax.nn.softmax(jnp.concatenate([s, s_new], axis=-1), axis=-1)
    val = jnp.where(col < ln, lat[:, :vw], 0.0)
    out = jnp.einsum("bht,bvt->bhv", probs[..., :-1], val)
    out = out + probs[..., -1:] * new[:, None, :vw].astype(jnp.float32)
    return out.astype(q.dtype)


def latent_attention(q, pages, page_table, lengths, new, *, value_width,
                     softmax_scale, layer=None, block_tokens=None,
                     impl=None):
    """Single-token absorbed latent attention straight over a latent
    page pool.

    q: [B, H, D] — the current token's queries with the key
        up-projection folded in, followed by their rotated part.
    pages: [num_pages, 1, D, page_len], or layer-stacked
        [L, num_pages, 1, D, page_len] with ``layer`` naming the layer.
    page_table: [B, max_pages] int32; lengths: [B] int32, the tokens
        already in the pool (a row handed 0 attends its own token only).
    new: [B, D] — the current token's latent vector, not yet pooled.
    value_width: the leading rows of a latent vector that are its values.
    impl: None (auto), "kernel" or "dense".

    Returns [B, H, value_width]: softmax attention over the row's
    ``lengths`` pooled tokens plus the current one, in latent space (the
    caller applies the value up-projection)."""
    b, heads, d = q.shape
    stacked = pages.ndim == 5
    if stacked != (layer is not None):
        raise ValueError(
            "latent_attention takes `layer` with a stacked [L, pages, 1, D, "
            f"page_len] pool and only then (pool rank {pages.ndim})")
    if not stacked:
        layer, pages = 0, pages[None]
    if pages.shape[2] != 1 or pages.shape[3] != d:
        raise ValueError(
            f"a latent pool is [.., pages, 1, {d}, page_len] for queries "
            f"{d} wide, got {pages.shape}")
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    page_len = pages.shape[-1]
    max_pages = page_table.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    page_table = jnp.asarray(page_table, jnp.int32)

    structure = f"page{page_len}"
    entry, key, source = tuning.lookup(
        KERNEL, structure, sq=b, sk=max_pages * page_len, d=d,
        dtype=pages.dtype, causal=True)
    bt = int(entry.get("block_k") or block_tokens or DEFAULT_BLOCK_TOKENS)
    ppb = max(1, min(bt // page_len, max_pages))
    aligned = page_len % 128 == 0 or _interpret()
    use_kernel = aligned if impl is None else impl == "kernel"
    if impl == "kernel" and not aligned:
        raise ValueError("latent_attention kernel needs page_len % 128 == 0 "
                         f"on TPU (got {page_len})")
    reason = None
    if not use_kernel:
        reason = ("impl='dense' requested" if impl == "dense"
                  else f"page_len {page_len} not a multiple of 128")
        log_fallback_on_tpu(KERNEL, "dense", reason)
    tuning.record_dispatch(
        KERNEL, structure, key, source, block_k=ppb * page_len,
        impl="kernel" if use_kernel else "dense", reason=reason)
    if use_kernel:
        return _latent_dma(q, pages, page_table, lengths, new, layer,
                           scale=softmax_scale, page_len=page_len, ppb=ppb,
                           vw=value_width)
    return _latent_dense(q, pages, page_table, lengths, new, layer,
                         scale=softmax_scale, vw=value_width)
