"""Flash-attention block-size sweep -> shape-keyed tuning artifact.

`bin/ds_tpu_bench kernels` entry point. Times candidate (block_q,
block_k) tilings of the Pallas flash-attention kernels at the model's
ACTUAL training shapes and writes a tuning artifact
(``ops.pallas.tuning`` format) whose winners the kernel dispatch
consults at trace time. Point ``$DS_TPU_KERNEL_TUNING_CACHE`` at the
artifact — or fold the winners into the committed default table
(``deepspeed_tpu/ops/pallas/flash_tuning_defaults.json``).

Method: a probe fwd+bwd at the requested shape tells us which kernel
STRUCTURES that shape dispatches to (resident/streamed/monolithic — read
back via ``tuning.last_dispatch``, so the sweep can never tune a
structure the shape doesn't use). Then per structure, each candidate is
injected as a runtime tuning-table entry and the kernel's own call is
re-traced and timed alone: the forward structure on the forward's call,
the backward structure on the backward's over one forward's residuals.

Everything but the timing numbers is CPU-runnable (interpret-mode
kernels): ``--trials 1`` with tiny shapes exercises the full plumbing in
CI; real numbers need hardware (``chiprun -- bin/ds_tpu_bench kernels``).
"""

import argparse
import functools
import time


def _divisor_candidates(dim, cap=1024):
    """128-aligned divisors of ``dim`` up to ``cap`` (the tilings
    ``pick_block`` can actually honor), largest-first; whole-dim for
    small/ragged sizes."""
    cands = [b for b in (1024, 512, 256, 128)
             if b <= min(dim, cap) and dim % b == 0]
    return cands or [dim]


def candidate_grid(structure, sq, sk):
    """(block_q, block_k) candidates for one kernel structure: every
    structure tiles both sides (the one-pass backward too, since its
    inner loop over k blocks stops at the diagonal: PR 45)."""
    return [(bq, bk) for bq in _divisor_candidates(sq)
            for bk in _divisor_candidates(sk)]


def _time_it(fn, args, trials, warmup):
    import jax
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(trials, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def sweep_flash_attention(batch, heads, sq, sk, head_dim, dtype="bfloat16",
                          causal=True, trials=3, warmup=1,
                          max_candidates=None, calls=1, log=print):
    """Returns {key: entry} tuning entries for every structure the shape
    dispatches to, each entry carrying the winning blocks, their ``ms``
    and every candidate's time under ``swept``.

    The kernels are timed ALONE, in the layout they run in ([batch,
    heads, seq, head_dim]: no transposes of the public layout around
    them): the forward structure on the forward's call, the backward
    structure on the backward's call(s) over the residuals of one
    forward. ``calls``: that many calls chained in one program (each
    call's first result is the next one's query, or the next one's
    cotangent), the time reported per call."""
    import importlib

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import flash_attention, tuning
    fa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")

    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (batch, heads, sq, head_dim), dt)
    k = jax.random.normal(ks[1], (batch, heads, sk, head_dim), dt)
    v = jax.random.normal(ks[2], (batch, heads, sk, head_dim), dt)
    g = jax.random.normal(ks[3], (batch, heads, sq, head_dim), dt)
    scale = head_dim ** -0.5

    def forward(q, k, v):
        return fa._flash_fwd(q, k, v, None, None, scale, causal, 0.0,
                             heads, None)

    def backward(q, k, v, o, lse, g):
        return fa._flash_bwd(scale, causal, 0.0, None, heads, False,
                             (q, k, v, None, None, o, lse), g)[:3]

    # one call's first result is shaped as the operand that the next
    # call takes from it (o as q, dq as g), so the calls cannot overlap
    # or be hoisted out of the loop
    fwd = jax.jit(lambda q, k, v: jax.lax.fori_loop(
        0, calls, lambda _, q: forward(q, k, v)[0], q))
    bwd = jax.jit(lambda q, k, v, o, lse, g: jax.lax.fori_loop(
        0, calls, lambda _, g: backward(q, k, v, o, lse, g)[0], g))

    # probe: which structures does this shape dispatch to? (through the
    # public entry point, as a model calls it)
    tuning.clear_last_dispatch()
    bshd = [jnp.swapaxes(t, 1, 2) for t in (q, k, v)]
    jax.block_until_ready(jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=causal)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(*bshd))
    dispatched = tuning.last_dispatch()
    fwd_structs = sorted(s for s in dispatched if s.startswith("fwd"))
    bwd_structs = sorted(s for s in dispatched if s.startswith("bwd"))
    log(f"shape b{batch} h{heads} sq{sq} sk{sk} d{head_dim} {dt.name} "
        f"{'causal' if causal else 'full'}: structures "
        f"{fwd_structs + bwd_structs}")
    o, lse = jax.jit(forward)(q, k, v)

    entries = {}

    def run(structure, timed_fn, args):
        key = dispatched[structure]["key"]
        cands = candidate_grid(structure, sq, sk)
        if max_candidates:
            cands = cands[:max_candidates]
        swept = []
        for bq, bk in cands:
            entry = {"block_q": bq, "block_k": bk}
            with tuning.tuning_table({key: entry}):
                jax.clear_caches()   # force a re-trace with the candidate
                try:
                    ms = _time_it(timed_fn, args, trials, warmup) / calls
                except Exception as e:  # infeasible tiling = skip, not fail
                    log(f"  {structure} bq={bq} bk={bk}: infeasible "
                        f"({str(e)[:200]})")
                    continue
                ran = tuning.last_dispatch()[structure]
            log(f"  {structure} bq={bq} bk={bk}: {ms:.3f} ms a call, tiles "
                f"{ran.get('tiles_visited')}/{ran.get('tiles_total')}")
            swept.append({**entry, "ms": round(ms, 4)})
        if not swept:
            raise RuntimeError(f"no feasible candidate for {structure}")
        entries[key] = {**min(swept, key=lambda e: e["ms"]), "swept": swept}

    for s in fwd_structs:
        run(s, fwd, (q, k, v))
    for s in bwd_structs:
        run(s, bwd, (q, k, v, o, lse, g))
    jax.clear_caches()
    return entries


def _head_block_candidates(kv_heads, group, dtype):
    """The K/V heads a grid step of the decode kernels may take, most
    first: what ``step_head_block`` makes of each size it can answer for
    this cache's type and ``group`` query heads a K/V head (5 and 10 only
    of a bf16 cache whose heads they divide)."""
    from deepspeed_tpu.ops.pallas._common import NARROW_HEAD_BLOCKS
    from deepspeed_tpu.ops.pallas.paged_attention import step_head_block
    return sorted({step_head_block(kv_heads, group, dtype, h)
                   for h in NARROW_HEAD_BLOCKS}, reverse=True)


def _paged_candidates(kv_heads, group, dtype, page_len, max_pages,
                      max_candidates=None):
    """(block_k tokens, head_block) candidates for the paged decode
    kernel: page_len multiples up to the table width (the DMA block the
    kernel double-buffers) crossed with ``_head_block_candidates``."""
    bks = [page_len * n for n in (1, 2, 4, 8) if n <= max_pages]
    cands = [(bk, hb) for bk in bks
             for hb in _head_block_candidates(kv_heads, group, dtype)]
    return cands[:max_candidates] if max_candidates else cands


def _sweep_decode_blocks(kernel, fn, args, key, cands, group, least, calls,
                         trials, warmup, log):
    """Time ``fn(*args)`` (``calls`` chained calls of a decode kernel)
    under each (block_k, head_block) of ``cands`` installed at ``key``;
    the winner's entry with ``bytes_us`` and every candidate under
    ``swept``: its rows a grid step, its time a call, and whether its
    result is, bit for bit, the first head block's at its ``block_k``
    (a row's arithmetic does not depend on which heads share its step)."""
    import numpy as np
    import jax
    from deepspeed_tpu.ops.pallas import tuning
    swept, first = [], {}
    for bk, hb in cands:
        entry = {"block_k": bk, "head_block": hb}
        with tuning.tuning_table({key: entry}):
            jax.clear_caches()   # force a re-trace with the candidate
            try:
                out = np.asarray(fn(*args), np.float32)
                ms = _time_it(fn, args, trials, warmup) / calls
            except Exception as e:  # infeasible tiling = skip, not fail
                log(f"  bk={bk} hb={hb}: infeasible ({str(e)[:300]})")
                continue
        same = np.array_equal(first.setdefault(bk, out), out)
        log(f"  bk={bk} hb={hb} ({hb * group} rows a step): {ms:.4f} ms, "
            f"{100 * least / (ms * 1e3):.1f}% of the bytes' time"
            f"{'' if same else '; BITS DIFFER from the first head block'}")
        swept.append({**entry, "rows": hb * group, "ms": round(ms, 5),
                      "same_bits": bool(same)})
    jax.clear_caches()
    if not swept:
        raise RuntimeError(f"no feasible {kernel} candidate")
    return {key: {**min(swept, key=lambda e: e["ms"]),
                  "bytes_us": round(least, 2), "swept": swept}}


def sweep_paged_attention(slots, heads, head_dim, page_len, max_pages,
                          dtype="float32", kv_int8=False, lengths=None,
                          calls=1, trials=3, warmup=1, max_candidates=None,
                          kv_heads=None, log=print):
    """Time candidate (block_k, head_block) tilings of the paged
    decode-attention kernel at one (slots x pages x head-dim) serving
    shape; returns {key: entry} in the shared tuning-artifact format
    (``block_k`` in TOKENS — pages_per_block = block_k / page_len), the
    winner's entry carrying every candidate's time under ``swept`` and
    whether its result (all ``calls`` chained) is, bit for bit, the first
    head block's at its ``block_k`` (``same_bits``).

    ``lengths``: the pooled tokens of the rows that decode, one number a
    row; the rows it does not name are at length 0, as the server hands
    the kernel the rows that do not decode. None = full tables at full
    lengths, the worst case. ``calls``: that many kernel calls chained
    in one program (each call's output is the next one's query), the
    time reported per call: a call of tens of microseconds is not timed
    by a host clock around one dispatch. The query and the current
    token's K/V are in the pool's type, as a model hands them.
    ``kv_heads``: the pool's heads where query heads are grouped on them
    (default: one K/V head a query head); a candidate's ``rows`` are the
    query-head rows of its grid step, and ``bytes_us`` is what the valid
    K and V columns take at the HBM's peak."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import paged_attention, tuning
    from deepspeed_tpu.ops.pallas.paged_attention import KERNEL

    kv_heads = kv_heads or heads
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads on {kv_heads} K/V heads: not "
                         "a whole group each")
    group = heads // kv_heads
    full = max_pages * page_len - 1
    if lengths is None:
        lengths = [full] * slots
    if len(lengths) > slots or max(lengths) > full:
        raise ValueError(f"lengths {lengths}: at most {slots} rows of at "
                         f"most {full} pooled tokens")
    num_pages = slots * max_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    dt = jnp.dtype(dtype)
    pool = (num_pages, kv_heads, head_dim, page_len)
    kp = jax.random.normal(ks[0], pool, dt)
    vp = jax.random.normal(ks[1], pool, dt)
    scales = {}
    if kv_int8:
        # THE scatter-side quantization rule (inference/cache.py) so the
        # timed path dequantizes exactly what serving would store
        from deepspeed_tpu.inference.cache import _quantize_kv
        kp, ksc = _quantize_kv(kp)
        vp, vsc = _quantize_kv(vp)
        scales = {"k_scale": ksc, "v_scale": vsc}
    ptab = (jnp.arange(slots * max_pages, dtype=jnp.int32) + 1) \
        .reshape(slots, max_pages)
    lengths = jnp.asarray(list(lengths) + [0] * (slots - len(lengths)),
                          jnp.int32)
    q = jax.random.normal(ks[2], (slots, 1, heads, head_dim), dt)
    kn = jax.random.normal(ks[3], (slots, kv_heads, head_dim, 1), dt)
    vn = jax.random.normal(ks[4], (slots, kv_heads, head_dim, 1), dt)

    def chain(q, *rest):
        return jax.lax.fori_loop(0, calls, lambda _, q: paged_attention(
            q, *rest, impl="kernel", **scales).astype(q.dtype), q)

    fn = jax.jit(chain)
    args = (q, kp, vp, ptab, lengths, kn, vn)
    tuning.clear_last_dispatch()
    jax.block_until_ready(fn(*args))
    dispatched = tuning.last_dispatch(KERNEL)
    structure = f"page{page_len}"
    key = dispatched[structure]["key"]
    # K and V, the columns under the lengths (an int8 pool's scale
    # planes, a 64th of it at most, are left out)
    least = (2 * int(lengths.sum()) * kv_heads * head_dim * kp.dtype.itemsize
             / HBM_BYTES_PER_S * 1e6)
    log(f"paged_attention slots{slots} h{heads} on {kv_heads} d{head_dim} "
        f"pages{max_pages}x{page_len} {dt.name}"
        f"{' int8' if kv_int8 else ''}: key {key}; the valid bytes' time "
        f"{least:.1f} us")

    return _sweep_decode_blocks(
        KERNEL, fn, args, key,
        _paged_candidates(kv_heads, group, kp.dtype, page_len, max_pages,
                          max_candidates),
        group, least, calls, trials, warmup, log)


# The grouped expert matmuls of the three expert cells (benchmarks/chip/
# configs): token-expert pairs a call, bf16 terms a pair (a float32 row
# goes as three), experts a layer, expert layers in the stack, and the
# [k, n] of the gate/up and of the down projection (float32 out but for
# OLMoE's gate/up). Decode calls first, then the chunk programs' calls.
GROUPED_SHAPES = {
    "olmoe-decode": (256, 1, 64, 8, 2048, 1024),
    "lfm2-decode": (128, 3, 64, 8, 2048, 1536),
    "kanana-decode": (192, 3, 128, 5, 2048, 768),
    "lfm2-chunk1": (512, 3, 64, 8, 2048, 1536),
    "lfm2-chunk4": (2048, 3, 64, 8, 2048, 1536),
    "kanana-chunk1": (768, 3, 128, 5, 2048, 768),
    "kanana-chunk4": (3072, 3, 128, 5, 2048, 768),
    "olmoe-chunk4": (4096, 1, 64, 8, 2048, 1024),
}
HBM_BYTES_PER_S = 819e9        # v5e (benchmarks/chip/peaks.json)


def drawn_groups(pairs, terms, experts, layers, seed=0, sigma=0.5):
    """Sizes ``[layers * experts]`` as a router gives them: ``pairs``
    drawn over one layer's experts with lognormal weights (the largest
    group two to five times the mean, some experts without a row at
    decode sizes), ``terms`` rows a pair; the other layers' groups
    empty."""
    import numpy as np
    rng = np.random.default_rng(seed)
    p = rng.lognormal(0.0, sigma, experts)
    sizes = rng.multinomial(pairs, p / p.sum()) * terms
    groups = np.zeros(layers * experts, np.int32)
    layer = layers // 2
    groups[layer * experts:(layer + 1) * experts] = sizes
    return groups


def sweep_grouped_matmul(pairs, terms, experts, layers, k, n, down=False,
                         out_dtype="float32", calls=16, trials=3, warmup=1,
                         candidates=None, log=print):
    """Time the grouped expert matmul alone at one call's shape — XLA's
    arm (``jax.lax.ragged_dot`` in calls of 128 rows, as
    ``moe/sharded_moe.py _ragged_matmul`` makes them) and the Pallas
    kernel at each ``(block_m, block_n)`` of ``candidates`` — ``calls``
    matmuls chained in one program (each one's result decides, in a way
    that never changes them, the next one's sizes), the time per call
    and the share of it that the call's bytes would take at the HBM's
    peak: the weights of the groups with rows once, plus rows in and
    out. ``down``: the down projection's ``[n, k]`` matrices. Returns
    {key: entry} in the tuning-artifact format, the winner's entry with
    every arm's time under ``swept``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.moe.sharded_moe import _ragged_matmul
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    from deepspeed_tpu.ops.pallas import tuning

    if down:
        k, n = n, k
    m, n_groups = pairs * terms, layers * experts
    out = jnp.dtype(out_dtype)
    sizes = drawn_groups(pairs, terms, experts, layers)
    touched = int((sizes > 0).sum())
    least = (touched * k * n * 2 + m * k * 2 + m * n * out.itemsize) \
        / HBM_BYTES_PER_S * 1e6
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    w = jax.random.normal(ks[1], (n_groups, k, n), jnp.bfloat16) * 0.02
    groups = jnp.asarray(sizes)

    def chained(matmul):
        # rows and weights are arguments: closed over, gigabytes of
        # weights would be constants of the program
        def run(rows, w, g):
            def body(_, carry):
                g, _ = carry
                y = matmul(rows, w, g)
                # never true: the next call waits for this one all the same
                return g + (y[0, 0] > 1e30).astype(g.dtype), y
            return jax.lax.fori_loop(
                0, calls, body, (g, jnp.zeros((m, n), out)))[1]
        return jax.jit(run)

    xla = chained(lambda r, x, g: _ragged_matmul(
        r, x, g, preferred_element_type=out))
    kernel = chained(lambda r, x, g: gm.grouped_matmul(r, x, g, out))
    args = (rows, w, groups)
    want = np.asarray(xla(*args), np.float32)
    us = _time_it(xla, args, trials, warmup) / calls * 1e3
    log(f"grouped_matmul {m} rows ({pairs} pairs x {terms}) x "
        f"[{n_groups}, {k}, {n}] -> {out.name}: {touched} groups with rows, "
        f"largest {sizes.max() / (m / experts):.2f} x the mean; the bytes' "
        f"time {least:.1f} us")
    log(f"  xla ragged_dot: {us:.1f} us a call, {100 * least / us:.1f}%")
    swept = [{"impl": "ragged_dot", "us": round(us, 2)}]
    key = gm.blocks(m, k, n, n_groups, rows.dtype, out)[2]
    if candidates is None:
        # (half of n as block_n read the same or worse at every row
        # tile: my chip run, PR 42)
        heights = (32, 64, 128) if m <= 1024 else (128, 256, 512)
        candidates = [(bm, n) for bm in heights if bm <= m]
    for bm, bn in candidates:
        entry = {"block_m": bm, "block_n": bn}
        with tuning.tuning_table({key: entry}):
            jax.clear_caches()   # force a re-trace with the candidate
            try:
                got = np.asarray(kernel(*args), np.float32)
                us = _time_it(kernel, args, trials, warmup) / calls * 1e3
            except Exception as e:  # infeasible tiling = skip, not fail
                log(f"  bm={bm} bn={bn}: infeasible ({str(e)[:200]})")
                continue
        err = float(np.abs(got - want).max())
        log(f"  bm={bm} bn={bn}: {us:.1f} us a call, "
            f"{100 * least / us:.1f}%; largest difference from xla's "
            f"{err:.3g}")
        swept.append({**entry, "us": round(us, 2), "max_diff": err})
    jax.clear_caches()
    best = min(swept[1:] or swept, key=lambda e: e["us"])
    return {key: {**best, "ms": round(best["us"] / 1e3, 5),
                  "bytes_us": round(least, 2), "swept": swept}}


def sweep_ring_append(rows=64, heads=10, head_dim=128, window=512,
                      dtype="bfloat16", live=(64, 32, 1), calls=16,
                      trials=3, warmup=1, log=print):
    """Time the ring's write alone (``ops/pallas/ring_append.py``) at one
    window layer's rings, ``[rows, heads, head_dim, window]`` for the keys
    and for the values, with each of ``live`` rows decoding (spread
    evenly over the slots, as a server's are): ``calls`` calls chained in
    one program over donated rings, the token's lane moving on by one a
    call, the time reported per call and per live row beside
    ``bytes_us_a_row``, what a row's two tiles read and written take at
    the HBM's peak. The first call of each count is held to a numpy
    reference bit for bit. Returns {key: entry} in the tuning artifact's
    format (there is no block to choose: ``swept`` holds the counts)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import tuning
    from deepspeed_tpu.ops.pallas.ring_append import (KERNEL, TILE,
                                                      ring_append)

    dt = jnp.dtype(dtype)
    shape = (rows, heads, head_dim, window)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    cols = tuple(jax.random.normal(k, shape[:3] + (1,), dt) for k in ks[2:])
    lane0 = jnp.asarray(np.random.default_rng(0).integers(0, window, rows),
                        jnp.int32)
    tile = min(TILE, window)
    least = 2 * 2 * heads * head_dim * tile * dt.itemsize \
        / HBM_BYTES_PER_S * 1e6

    def chain(rings, cols, lane, mask, n):
        return jax.lax.fori_loop(0, n, lambda i, r: ring_append(
            r, cols, (lane + i) % window, mask), rings)

    # (the rings are donated, so each timed call takes the last one's:
    # ``_time_it`` hands one set of arguments to every call)
    fn = jax.jit(chain, donate_argnums=(0,), static_argnums=(4,))
    fresh = lambda: tuple(jax.random.normal(k, shape, dt) for k in ks[:2])
    swept, key = [], None
    for n_live in live:
        mask = np.zeros(rows, bool)
        mask[np.linspace(0, rows - 1, n_live).round().astype(int)] = True
        args = (cols, lane0, jnp.asarray(mask))
        before = [np.array(r) for r in fresh()]
        after = fn(fresh(), *args, 1)
        key = tuning.last_dispatch(KERNEL)["tile"]["key"]
        for want, got, col in zip(before, after, cols):
            for b in np.flatnonzero(mask):
                want[b, :, :, int(lane0[b])] = np.array(col)[b, :, :, 0]
            if not np.array_equal(want, np.array(got)):
                raise AssertionError(
                    f"ring_append at {key} with {n_live} live rows differs "
                    "from the numpy reference")
        rings = fresh()
        for _ in range(max(warmup, 1)):
            rings = jax.block_until_ready(fn(rings, *args, calls))
        best = float("inf")
        for _ in range(max(trials, 1)):
            t0 = time.perf_counter()
            rings = jax.block_until_ready(fn(rings, *args, calls))
            best = min(best, time.perf_counter() - t0)
        us = best * 1e6 / calls
        log(f"ring_append {key}: {n_live} live rows {us:.1f} us a call, "
            f"{us / n_live:.2f} us a row (its bytes' time {least:.2f}: "
            f"{100 * least * n_live / us:.1f}%)")
        swept.append({"live": n_live, "us": round(us, 2),
                      "us_a_row": round(us / n_live, 3)})
    return {key: {"tile": tile, "bytes_us_a_row": round(least, 3),
                  "swept": swept}}


def sweep_decode_attention(slots, heads, head_dim, seq, dtype="bfloat16",
                           lengths=None, calls=1, trials=3, warmup=1,
                           max_candidates=None, kv_heads=None, log=print):
    """Time candidate (block_k, head_block) tilings of the contiguous
    decode kernel (``ops/pallas/decode_attention.py``) over caches
    ``[slots, kv_heads, head_dim, seq]`` — Phi-4-mini-flash's window rings
    are ``[64, 10, 128, 512]`` at lengths 512 — with ``sweep_paged_
    attention``'s conventions: ``lengths`` the valid tokens of the rows
    that decode (the rows not named at 0; None = every row full),
    ``calls`` calls chained in one program (each call's output is the
    next one's query), the time per call, ``bytes_us`` what the valid K
    and V columns take at the HBM's peak, ``same_bits`` whether a
    candidate's result is the first head block's at its ``block_k``.
    Returns {key: entry} in the tuning artifact's format, every candidate
    under ``swept``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import decode_attention, tuning
    from deepspeed_tpu.ops.pallas.decode_attention import KERNEL

    kv_heads = kv_heads or heads
    group = heads // kv_heads
    if lengths is None:
        lengths = [seq] * slots
    if len(lengths) > slots or max(lengths) > seq:
        raise ValueError(f"lengths {lengths}: at most {slots} rows of at "
                         f"most {seq} tokens")
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k = jax.random.normal(ks[0], (slots, kv_heads, head_dim, seq), dt)
    v = jax.random.normal(ks[1], (slots, kv_heads, head_dim, seq), dt)
    q = jax.random.normal(ks[2], (slots, 1, heads, head_dim), dt)
    lengths = jnp.asarray(list(lengths) + [0] * (slots - len(lengths)),
                          jnp.int32)

    def chain(q, *rest):
        return jax.lax.fori_loop(0, calls, lambda _, q: decode_attention(
            q, *rest).astype(q.dtype), q)

    fn = jax.jit(chain)
    args = (q, k, v, lengths)
    tuning.clear_last_dispatch()
    jax.block_until_ready(fn(*args))
    key = tuning.last_dispatch(KERNEL)["dma"]["key"]
    least = (2 * int(lengths.sum()) * kv_heads * head_dim * dt.itemsize
             / HBM_BYTES_PER_S * 1e6)
    log(f"decode_attention slots{slots} h{heads} on {kv_heads} d{head_dim} "
        f"s{seq} {dt.name}: key {key}; the valid bytes' time {least:.1f} us")
    cands = [(bk, hb) for bk in _divisor_candidates(seq)[::-1]
             for hb in _head_block_candidates(kv_heads, group, dt)]
    return _sweep_decode_blocks(
        KERNEL, fn, args, key,
        cands[:max_candidates] if max_candidates else cands, group, least,
        calls, trials, warmup, log)


def _int_list(text):
    return [int(x) for x in str(text).split(",") if x]


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="ds_tpu_bench kernels",
        description="attention block-size sweep -> tuning artifact")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="paged sweep: the pool's K/V heads where query "
                        "heads are grouped on them (default: --heads)")
    p.add_argument("--head-dim", type=_int_list, default=[128],
                   help="head dim, or a comma-separated grid")
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--kv-seq", type=int, default=None,
                   help="key length (default: --seq)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--no-causal", action="store_true")
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--max-candidates", type=int, default=None,
                   help="cap the per-structure candidate grid (CI smoke)")
    p.add_argument("--kernel", choices=["flash_attention",
                                        "paged_attention",
                                        "grouped_matmul", "ring_append",
                                        "decode_attention", "all"],
                   default="flash_attention",
                   help="which kernel family to sweep; paged_attention "
                        "sweeps the serving decode kernel over the "
                        "--slots x --max-pages x --head-dim grid")
    p.add_argument("--slots", type=_int_list, default=[8],
                   help="paged sweep: comma-separated slot counts")
    p.add_argument("--max-pages", type=_int_list, default=[16],
                   help="paged sweep: comma-separated page-table widths")
    p.add_argument("--page-len", type=int, default=128)
    p.add_argument("--kv-int8", action="store_true",
                   help="paged sweep: time the int8-page dequant path")
    p.add_argument("--lengths", type=_int_list, default=None,
                   help="paged sweep: pooled tokens of the rows that decode, "
                        "comma-separated (the other rows at length 0; "
                        "default: every row at the full table)")
    p.add_argument("--calls", type=int, default=1,
                   help="kernel calls chained in one timed program (the "
                        "time is per call)")
    p.add_argument("--live", type=_int_list, default=[64, 32, 1],
                   help="ring_append sweep: counts of rows that decode "
                        "(of --slots rows of --kv-heads x --head-dim x "
                        "--seq rings)")
    p.add_argument("--shapes", default=",".join(GROUPED_SHAPES),
                   help="grouped_matmul sweep: which of "
                        f"{', '.join(GROUPED_SHAPES)}")
    p.add_argument("--out", default="benchmarks/results/flash_tuning.json")
    args = p.parse_args(argv)

    import jax
    from deepspeed_tpu.ops.pallas import tuning
    from deepspeed_tpu.ops.pallas._common import on_tpu

    head_dims = (args.head_dim if isinstance(args.head_dim, list)
                 else [args.head_dim])
    entries = {}
    if args.kernel in ("flash_attention", "all"):
        for hd in head_dims:
            entries.update(sweep_flash_attention(
                args.batch, args.heads, args.seq, args.kv_seq or args.seq,
                hd, dtype=args.dtype, causal=not args.no_causal,
                trials=args.trials, warmup=args.warmup,
                max_candidates=args.max_candidates, calls=args.calls))
    if args.kernel in ("paged_attention", "all"):
        # the serving-shape grid: pages x slots x head-dim (each combo
        # is its own shape key, so one hardware window tunes them all)
        for slots in args.slots:
            for max_pages in args.max_pages:
                for hd in head_dims:
                    entries.update(sweep_paged_attention(
                        slots, args.heads, hd, args.page_len, max_pages,
                        dtype=args.dtype, kv_int8=args.kv_int8,
                        lengths=args.lengths, calls=args.calls,
                        trials=args.trials, warmup=args.warmup,
                        max_candidates=args.max_candidates,
                        kv_heads=args.kv_heads))
    if args.kernel == "grouped_matmul":
        # (not under "all": its stacks of weights are gigabytes)
        for name in args.shapes.split(","):
            pairs, terms, experts, layers, k, n = GROUPED_SHAPES[name]
            for down in (False, True):
                bf16_out = name.startswith("olmoe") and not down
                entries.update(sweep_grouped_matmul(
                    pairs, terms, experts, layers, k, n, down=down,
                    out_dtype="bfloat16" if bf16_out else "float32",
                    calls=args.calls, trials=args.trials,
                    warmup=args.warmup))
    if args.kernel == "decode_attention":
        # (not under "all": one serving shape has rings, and names it)
        for slots in args.slots:
            for hd in head_dims:
                entries.update(sweep_decode_attention(
                    slots, args.heads, hd, args.seq, dtype=args.dtype,
                    lengths=args.lengths, calls=args.calls,
                    trials=args.trials, warmup=args.warmup,
                    max_candidates=args.max_candidates,
                    kv_heads=args.kv_heads))
    if args.kernel == "ring_append":
        # (not under "all": it has no block to choose, only a time)
        for slots in args.slots:
            for hd in head_dims:
                entries.update(sweep_ring_append(
                    slots, args.kv_heads or args.heads, hd, args.seq,
                    dtype=args.dtype,
                    live=[n for n in args.live if n <= slots],
                    calls=args.calls, trials=args.trials,
                    warmup=args.warmup))
    device = jax.devices()[0].device_kind if on_tpu() else "cpu-interpret"
    tuning.save_artifact(
        args.out, entries, device=device,
        kind=f"{args.kernel}_block_sweep",
        shape={"batch": args.batch, "heads": args.heads,
               "kv_heads": args.kv_heads, "seq": args.seq,
               "kv_seq": args.kv_seq or args.seq,
               "head_dim": args.head_dim, "dtype": args.dtype,
               "causal": not args.no_causal,
               "slots": args.slots, "max_pages": args.max_pages,
               "page_len": args.page_len, "kv_int8": args.kv_int8,
               "lengths": args.lengths, "calls": args.calls},
        trials=args.trials,
        note=("interpret-mode timings are NOT representative — regenerate "
              "on hardware" if device == "cpu-interpret" else
              "point $DS_TPU_KERNEL_TUNING_CACHE at this file or fold the "
              "winners into flash_tuning_defaults.json"))
    print(f"wrote {len(entries)} tuning entr"
          f"{'y' if len(entries) == 1 else 'ies'} -> {args.out}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
