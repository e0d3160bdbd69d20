"""Shared kernel-dispatch helpers."""

import jax

# The additive masked-out encoding shared by the attention kernels and the
# mask->bias folding in ops.transformer.attention: kernels classify a row
# as fully masked via thresholds on NEG_INF/2, so every producer of masked
# logits must use THIS constant (fp32- and bf16-representable).
NEG_INF = -1e30


def on_tpu() -> bool:
    """True exactly when JAX's default backend is ``tpu``. A backend
    that fails to initialise raises here — it must never read as "not a
    TPU" and turn every kernel into an interpreted one."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas kernels interpret off-TPU so the suite runs on the CPU mesh."""
    return not on_tpu()


def log_fallback_on_tpu(kernel: str, choice: str, reason: str):
    """On TPU, say once that a dispatch did not take its Pallas kernel
    and why — the log-side twin of the ``tuning.record_dispatch`` entry
    the chip smoke reads."""
    if on_tpu():
        from ...utils.logging import warn_once
        warn_once(f"{kernel}: running the {choice} path on TPU ({reason})")


def in_manual_region() -> bool:
    """True while tracing inside a shard_map (Manual mesh axes)."""
    from jax.sharding import get_abstract_mesh
    am = get_abstract_mesh()
    return (not am.empty
            and any("Manual" in str(t) for t in am.axis_types))


def model_axis_size(mesh, heads: int) -> int:
    """Degree ``mesh``'s ``model`` axis splits a decode kernel's head dim
    to; 1 with no mesh. The caller that owns the mesh passes it — the
    kernels read no global."""
    tp = 1 if mesh is None else mesh.shape.get("model", 1)
    if heads % tp != 0:
        raise ValueError(
            f"{heads} attention heads cannot be split over a model axis "
            f"of {tp}")
    return tp


def over_model_axis(fn, mesh, in_specs, out_specs):
    """``fn`` mapped over ``mesh`` when its ``model`` axis is live, else
    ``fn`` itself. Mosaic kernels cannot be partitioned automatically
    (jax refuses to compile one under a multi-device jit), so the decode
    kernels run one head shard per device. Specs name only ``"model"``:
    across every other axis the operands are replicated and each device
    computes the same shard, as serving does today. With no split the
    program stays on the devices its operands already live on."""
    if mesh is None or mesh.shape.get("model", 1) == 1:
        return fn
    from ...utils.jax_compat import shard_map
    return shard_map(fn, mesh, in_specs, out_specs)


def pick_block(dim: int, want: int) -> int:
    """Largest divisor of ``dim`` <= ``want`` keeping 128-lane alignment
    (whole dim for small/ragged sizes) — the shared tiling heuristic for
    the flash / int8-matmul kernels."""
    import math
    b = math.gcd(dim, min(want, dim))
    return b if b % 128 == 0 or b == dim else dim


# the head blocks that run compiled on the v5e: the powers of two on both
# arms of ``online_softmax_block`` (1 to 8 since PR 21, 16 since PR 51),
# five and ten on the narrow arm alone (PR 58)
HEAD_BLOCKS = (16, 8, 4, 2, 1)
NARROW_HEAD_BLOCKS = (16, 10, 8, 5, 4, 2, 1)


def pick_head_block(heads: int, want: int, narrow: bool = False) -> int:
    """Heads per grid step for the decode kernels: the largest of 1, 2, 4,
    8 and 16 — and, where the cache's products are ``narrow``
    (``products_dtype``: a bf16 cache), of 5 and 10 — that divides
    ``heads`` and ``want``. The first four run compiled on the v5e since
    PR 21 and ``chip_smoke.py``'s kernels phase keeps them covered: GPT-2's
    12 heads get 4, and 2 / 1 under ``mp_size`` 2 / 4. Sixteen runs there
    since PR 51, over a bf16 pool (the paged kernel's sweep and the three
    sixteen-head serving cells), and is answered only to a caller that
    asks for it: at a ``want`` of 8 every answer is what it was.

    Five and ten run there since PR 58, over a bf16 pool and bf16 rings of
    ten cached heads with four query rows each (Phi-4-mini-flash: 20 and
    40 rows a step in both kernels' sweeps, 40 in ``serve-phi4flash-
    reason`` and the smoke's phases). They divide no constant, so they are
    answered only to a table entry or a caller that asks: a ten-head bf16
    cache gets 10 at a ``want`` of 10, 5 at 5 — or at 10 on the five heads
    a device holds under ``mp_size`` 2 — and 2 at 8 or 16 as before. A
    float32 or int8 cache of ten heads gets 2 whatever is asked: neither
    size was compiled on that arm.

    Mosaic (jax 0.9.0) aborted the process at 12 (PR 21, on the chip,
    ``limits[i] <= dim(i)``). What is known since PR 49, from compiles for
    a described v5e: that check is ``online_softmax_block``'s float32 arm
    cutting one row of its boolean mask at a row past the eighth — a
    float32 pool at a head block of 12 or 16 still aborts on it
    (``paged_attention.step_head_block`` caps its ``want`` at eight rows
    before it asks here), a bf16 pool (whose arm never cuts the mask)
    compiles at both. Whether PR 21's kernel, which had one arm, died of
    that same cut was not gone back to; twelve has not run on the chip and
    no cell holds twelve heads a device, so it is not chosen on either
    arm."""
    import math
    both = math.gcd(heads, want)
    return next(b for b in (NARROW_HEAD_BLOCKS if narrow else HEAD_BLOCKS)
                if both % b == 0)


def read_slopes(slopes_ref, h0: int, hb: int):
    """[hb, 1] ALiBi slope column for one head block from a prefetched
    [H] slope vector (shared by the decode / paged-decode kernels)."""
    import jax.numpy as jnp
    return jnp.stack([slopes_ref[h0 + h] for h in range(hb)]).reshape(hb, 1)


def products_dtype(dtype):
    """The type ``online_softmax_block`` multiplies a K/V block of
    ``dtype`` in: the block's own when it is a float narrower than
    float32 (a bf16 pool: one pass of the MXU, float32 accumulation),
    else float32."""
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    narrow = jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize < 4
    return dtype if narrow else jnp.dtype(jnp.float32)


def block_query(q, scale, kv_dtype):
    """A grid step's query rows as ``online_softmax_block`` takes them,
    float32 ``[rows, d]``: pre-scaled where the products are float32;
    as they arrived where the products run in a narrower pool's type —
    the block casts them back to it (exact: they came in it) and the
    scale goes on the float32 scores."""
    import jax.numpy as jnp
    q = q.astype(jnp.float32)
    return q * scale if products_dtype(kv_dtype) == jnp.float32 else q


def online_softmax_block(q, kblk, vblk, start, valid_len, q_pos, slopes,
                         m_ref, l_ref, acc_ref, *, scale, hb, alibi, group=1):
    """One online-softmax update for an [hb, d, Bk] K^T/V block — THE
    inner loop shared by the decode-attention and paged-attention
    kernels (one definition, or the two online-softmax recurrences
    silently drift).

    All matmuls run in the blocks' own dtype (``products_dtype``: the
    bf16 hot path) with fp32 accumulation — the same bf16-in/fp32-acc
    contract as the flash kernels and the XLA einsum path — and lose
    nothing to it: a bf16 block goes to the MXU as it lies in VMEM
    against the query in bf16 (bf16 x bf16 products are exact in
    float32; ``scale`` and ALiBi go on the float32 scores), and the
    float32 probabilities go as their three bf16 terms, stacked as rows
    of one product (the MXU's time is the block's load, not the rows
    pushed through it). A float32 block (and an int8 page, dequantized
    BEFORE this call) multiplies in float32 as it always did.

    q is ``block_query``'s [hb, d] fp32; ``kblk``/``vblk`` are [hb, d,
    Bk] refs or arrays. Per-head scores are hb small matmuls (MHA has
    distinct K per head, so there is no single big matmul); the
    softmax/statistics update is vectorized across the head block.

    ``group`` query heads read each K/V head (grouped-query attention):
    q, the statistics and the accumulator then hold ``hb * group`` rows,
    K/V head ``h``'s group in rows ``[h * group, (h + 1) * group)``, and
    a head's block is multiplied once for its whole group.

    ``valid_len`` masks columns (``start + i < valid_len`` attend);
    ``q_pos`` is the query's absolute position — the ALiBi center
    (``slope * (col - q_pos)``). The single-token decode kernel attends
    a cache that already holds the current token, so it passes
    ``valid_len=length, q_pos=length-1``; the paged kernel attends
    pool pages EXCLUDING the current token and folds it in separately,
    so it passes ``valid_len=length, q_pos=length``.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    cdt = products_dtype(kblk.dtype)
    narrow = cdt != jnp.float32
    rows = []
    for h in range(hb):
        kh = kblk[h].astype(cdt)                             # [d, Bk]
        rows.append(jnp.dot(q[h * group:(h + 1) * group].astype(cdt), kh,
                            preferred_element_type=jnp.float32))  # [g, Bk]
    s = jnp.concatenate(rows, axis=0)                        # [hb*g, Bk]
    if narrow:
        s = s * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + start
    if alibi:
        s = s + slopes * (col - q_pos).astype(jnp.float32)
    valid = col < valid_len
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                      # [hb, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                   # [hb, Bk]
    # columns past the valid prefix may hold padding garbage —
    # 0-probability x NaN = NaN, so zero the V columns explicitly
    nt = (((1,), (1,)), ((), ()))
    outs = []
    if narrow:
        # p as the three bf16 terms whose sum it is to float32's 24 bits
        terms, rest = [], p
        for _ in range(3):
            terms.append(rest.astype(cdt).astype(jnp.float32))
            rest = rest - terms[-1]
        for h in range(hb):
            # the guard is a select on the block's BITS, two bf16 rows a
            # word: no widened copy of the block is made (and Mosaic
            # lays no one-row boolean mask over a bf16 tile). It costs
            # less than a branch around it would: every block takes it
            bits = pltpu.bitcast(vblk[h], jnp.uint32)        # [d/2, Bk]
            ok = (jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
                  + start) < valid_len
            vh = pltpu.bitcast(jnp.where(ok, bits, jnp.uint32(0)), cdt)
            hs = slice(h * group, (h + 1) * group)
            ph = jnp.concatenate([t[hs] for t in terms], axis=0)
            o = jax.lax.dot_general(ph.astype(cdt), vh, nt,
                                    preferred_element_type=jnp.float32)
            outs.append(o[2 * group:] + o[group:2 * group] + o[:group])
    else:
        for h in range(hb):
            vh = jnp.where(valid[h * group:h * group + 1],
                           vblk[h].astype(jnp.float32), 0.0)
            outs.append(jax.lax.dot_general(
                p[h * group:(h + 1) * group], vh, nt,
                preferred_element_type=jnp.float32))             # [g, d]
    pv = jnp.concatenate(outs, axis=0)                       # [hb*g, d]
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + pv
    m_ref[...] = m_new
