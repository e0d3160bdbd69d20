"""Quickest proof that the system still starts on the chip.

One process. Drives the normal entry points once at the full published
width of gpt2-125m with seeded weights and seeded tokens (no network):

- train:   ``ds.initialize`` -> ``engine.train_batch`` (bf16, ZeRO-1,
           seq 1024, micro 32, chunked vocab loss), a few steps on a fixed
           batch;
- serve:   ``ds.init_inference(...).serve({...paging...})``, more
           mixed-length requests than slots, ``submit`` -> ``run``, checked
           against the float32 reference at the logit level;
- olmoe:   the same serving path with OLMoE-1B-7B's block at its
           published widths, two layers deep: a dropless top-8 router
           over 64 SwiGLU experts, RMSNorm, QK-norm and RoPE on the paged
           path, the grouped expert matmul one Pallas call a matmul
           (``ops/pallas/grouped_matmul.py``) compiled by Mosaic;
- lfm2:    the same serving path with LFM2-24B-A2B's first four layers
           at published widths (conv, conv, attention, conv; two dense
           and two expert layers): the gated short convolution's state
           beside the paged K/V of 8 heads that 32 query heads read in
           groups, a sigmoid top-4 router with its bias, and a prefix hit
           that restores the state;
- falcon_h1: the same serving path with Falcon-H1-34B's block at its
           published widths, two layers deep on an eighth of the
           vocabulary: a Mamba-2 mixer beside grouped-query attention,
           the mixer's matrix state a slot updated in place by a Mosaic
           kernel, and a prefix hit that starts from a snapshot;
- phi4flash: the same serving path with Phi-4-mini-flash's layers at
           published widths, eight layers deep (mixers 0, 2, 4, window
           layers 1 and 3, the full layer 5, a memory unit and a cross
           layer) on an eighth of the vocabulary: the paged kernel over
           ten cached heads of 128 with four query rows each (the full
           layer's call and the cross layer's, on the same pages), the
           contiguous decode kernel on a ring of 512 that wraps, and the
           refusals by name;
- kernels: every Pallas kernel compiled by Mosaic and run once at a real
           shape against its jnp reference;
- offload: offload configs really place state in ``pinned_host``, or
           refuse by name;
- several chips (``jax.device_count() >= 4``): gpt2-1.3b ZeRO-3 over all
           of them, and tensor-parallel serving.

Exits nonzero, and prints no result line, when JAX's platform is not
``tpu`` or when any phase failed. On success the last line of stdout is
``{"ok": true, "device": {...}}``. Figures printed on the way (step time,
GB/s) are smoke readings, not benchmark results.
"""

import contextlib
import dataclasses
import faulthandler
import json
import math
import os
import sys
import time
import traceback

# The driver allows 1200 s; leave room for interpreter exit.
TOTAL_BUDGET_S = 1150

FULL = {
    "train": dict(preset="gpt2-125m", seq=1024, micro=32, steps=4,
                  n_layers=None),
    # max_len 1024: a slot holds the prompt of four pages and a little
    # that is admitted alone and goes in as one wide chunk
    "serve": dict(preset="gpt2-125m", num_slots=4, max_len=1024,
                  page_len=128, n_requests=10, prompt_max=300, new_max=24,
                  paging_kernel="auto", n_layers=None, logit_tol=0.1),
    "olmoe": dict(n_layers=2, num_slots=4, max_len=1024, page_len=128,
                  n_requests=10, prompt_max=300, new_max=24,
                  paging_kernel="auto", logit_tol=0.1),
    # max_len 2048: a pool of 65 pages, so that one layer's K pages (17
    # MB in float32) stay well over the decode program's scratch (6.6 MB
    # of float32 activations: logits over 65,536 words, the experts' rows)
    "lfm2": dict(n_layers=4, num_slots=4, max_len=2048, page_len=128,
                 n_requests=10, prompt_max=300, new_max=24,
                 paging_kernel="auto", logit_tol=0.1),
    # max_len 2048: a pool of 65 pages, so that one layer's latent pages
    # (19 MB in float32) stay over the decode program's scratch (12.6 MB:
    # 32 rows of float32 logits over 128,256 words would be 16)
    "kanana": dict(n_layers=3, num_slots=4, max_len=2048, page_len=128,
                   n_requests=10, prompt_max=300, new_max=24,
                   paging_kernel="auto", logit_tol=0.1),
    # max_len 2048: a pool of 65 pages (one layer's K pages 8.5 MB in
    # bf16); four snapshots, so that the first request's leaf outlives
    # the leaves of the requests between it and the one that hits
    "falcon_h1": dict(n_layers=2, num_slots=4, max_len=2048, page_len=128,
                      n_requests=10, prompt_max=300, new_max=24,
                      paging_kernel="auto", logit_tol=0.1),
    # max_len 2048: a pool of 65 pages; prompts to 300 and the long one of
    # 544 + 8 tokens pass the window of 512, so a ring wraps
    "phi4flash": dict(n_layers=8, num_slots=4, max_len=2048, page_len=128,
                      n_requests=10, prompt_max=300, new_max=24,
                      paging_kernel="auto", logit_tol=0.1),
    "kernels": dict(seq=1024, heads=12, batch=2, cache_len=1024,
                    gemv_k=4096, gemv_n=16384, sparse_seq=2048,
                    gemv_timeout_s=180),
    "offload": dict(preset="gpt2-125m", n_layers=2, seq=256, micro=4),
    "multichip": dict(preset="gpt2-1.3b", seq=1024, micro=4, steps=3,
                      n_layers=None,
                      serve=dict(preset="gpt2-1.3b", n_layers=4, num_slots=4,
                                 max_len=1024, page_len=128, n_requests=8,
                                 prompt_max=200, new_max=16,
                                 paging_kernel="auto", logit_tol=0.1)),
}

_T0 = time.monotonic()


def _say(msg):
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def _budget_left():
    return max(1.0, TOTAL_BUDGET_S - (time.monotonic() - _T0))


@contextlib.contextmanager
def _deadline(seconds):
    """Fail — dump every thread's stack and ``_exit(1)`` — if the body
    outlives ``seconds`` (or the run's total budget). A kernel that hangs
    the chip blocks in native code, where no Python exception reaches."""
    faulthandler.dump_traceback_later(min(seconds, _budget_left()),
                                      exit=True)
    try:
        yield
    finally:
        faulthandler.dump_traceback_later(_budget_left(), exit=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _mosaic(rec, what):
    """A dispatch record says a Pallas kernel ran compiled, not
    interpreted and not swapped for a jnp path."""
    _check(rec, f"{what}: no dispatch record — the kernel never traced")
    _check(rec.get("interpret") is False,
           f"{what}: ran in interpret mode ({rec})")
    _check(rec.get("impl", "kernel") == "kernel",
           f"{what}: dispatched {rec.get('impl')!r}, not the kernel ({rec})")


def _grouped_matmul_traced():
    from deepspeed_tpu.observability.metrics import get_registry
    return get_registry().counter("moe/grouped_matmul_traced/kernel").value


def _grouped_matmul_engaged(label, n_groups, traced_before):
    """The expert layers' grouped matmul went to the Pallas kernel
    (``ops/pallas/grouped_matmul.py``), compiled and not interpreted:
    the dispatch record of a stack of ``n_groups`` matrices, and the
    per-compile counter against its reading before the phase."""
    from deepspeed_tpu.ops.pallas import tuning
    rec = tuning.last_dispatch("grouped_matmul").get(f"groups{n_groups}")
    _mosaic(rec, f"{label} grouped matmul")
    _check(rec.get("block_m") and rec.get("block_n"),
           f"{label} grouped matmul: the record names no tiles ({rec})")
    traced = _grouped_matmul_traced() - traced_before
    _check(traced > 0, f"{label}: moe/grouped_matmul_traced/kernel did not "
                       "move: no program traced the kernel")
    _say(f"{label}: grouped matmul {rec}, traced {traced} times")


def _close(name, got, want, tol):
    """``got`` within ``tol`` of ``want``, relative to ``want``'s scale."""
    import numpy as np
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    _check(got.shape == want.shape,
           f"{name}: shape {got.shape} != reference {want.shape}")
    _check(np.isfinite(got).all(), f"{name}: non-finite values")
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    _check(err <= tol, f"{name}: max err {err:.3g} of scale exceeds {tol}")
    return err


def _gpt(preset, n_layers, **kw):
    from deepspeed_tpu.models import GPT, GPT2_PRESETS
    cfg = GPT2_PRESETS[preset]
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return GPT(dataclasses.replace(cfg, **kw))


def _loss_fn(model, params, batch, rng, train):
    """Next-token loss with the chunked vocab head: the model runs on
    ``seq`` tokens (128-aligned, flash-eligible) and the labels are the
    same window shifted by one, so the chunk divides and [B, S, V] logits
    never materialize."""
    from deepspeed_tpu.models import gpt_chunked_loss_fn
    ids = batch["input_ids"]
    h, wte = model.apply(params, ids[:, :-1], deterministic=not train,
                         return_hidden=True)
    return gpt_chunked_loss_fn(h, wte, ids[:, 1:], chunk=128)


def _trainer(preset, n_layers, seq, micro, zero, param_dtype, **config):
    """``ds.initialize`` on a seeded model and one fixed seeded batch of
    ``seq + 1`` tokens per row (see ``_loss_fn``): (engine, batch, vocab).
    The recipe is ``bench.py:_train_bench``'s — bf16, full remat, scanned
    layers, Adam 1e-4, no accumulation."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    model = _gpt(preset, n_layers, dtype=jnp.bfloat16,
                 param_dtype=param_dtype, scan_layers=True, remat="full",
                 max_seq_len=seq)
    rows = micro * jax.device_count()
    vocab = model.config.vocab_size
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, vocab, size=(rows, seq + 1), dtype=np.int32)}
    engine, _, _, _ = ds.initialize(
        model=model, loss_fn=_loss_fn, rng=jax.random.PRNGKey(0),
        sample_batch={"input_ids": batch["input_ids"][:1, :-1]},
        config={"train_batch_size": rows,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": zero,
                "steps_per_print": 10_000, **config})
    return engine, batch, vocab


def _seeded_params(model):
    import jax
    import jax.numpy as jnp
    import flax.core.meta as flax_meta
    return jax.jit(lambda r: flax_meta.unbox(model.init(
        r, jnp.ones((1, 8), jnp.int32)))["params"])(jax.random.PRNGKey(0))


def _train_steps(engine, batch, steps):
    import jax
    losses, times = [], []
    for _ in range(steps):
        t0 = time.monotonic()
        loss = engine.train_batch(batch)
        jax.block_until_ready((loss, engine.params))
        times.append(time.monotonic() - t0)
        losses.append(float(loss))
    return losses, times


def _check_losses(losses, vocab):
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite loss: {losses}")
    _check(abs(losses[0] - math.log(vocab)) < 0.7,
           f"first loss {losses[0]:.3f} not near ln({vocab}) = "
           f"{math.log(vocab):.3f}")
    _check(losses[-1] < losses[0],
           f"loss did not fall on a fixed batch: {losses}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def phase_train(preset, seq, micro, steps, n_layers):
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import tuning

    tuning.clear_last_dispatch()
    engine, batch, vocab = _trainer(preset, n_layers, seq, micro,
                                    {"stage": 1}, jnp.float32)
    losses, times = _train_steps(engine, batch, steps)
    _say(f"train {preset}: losses {[round(x, 3) for x in losses]}; step "
         f"times s {[round(t, 3) for t in times]} (first includes compile)")
    _check_losses(losses, vocab)
    choice = tuning.last_dispatch("attention").get("backend")
    flash = tuning.last_dispatch("flash_attention")
    fwd = [s for s in flash if s.startswith("fwd_")]
    bwd = [s for s in flash if s.startswith("bwd_")]
    _check(choice and choice["backend"] == "pallas",
           f"training attention did not dispatch the flash kernel: {choice}")
    _check(fwd and bwd, f"flash fwd+bwd structures not both traced: {flash}")
    for s in fwd + bwd:
        _mosaic(flash[s], f"flash_attention/{s}")
    for s in fwd + bwd:
        rec = flash[s]
        _say(f"train: flash {s} as run: block_q {rec.get('block_q')}, "
             f"block_k {rec.get('block_k')}, tiles "
             f"{rec.get('tiles_visited')}/{rec.get('tiles_total')} a "
             f"(batch, head), table {rec['source']}")
        # a causal call of more than one tile a side computes nothing
        # above the diagonal (PR 45: the one-pass backward too)
        _check(max(rec["block_q"], rec["block_k"]) >= seq
               or rec["tiles_visited"] < rec["tiles_total"],
               f"flash {s} visits every tile of a causal call: {rec}")
    engine.destroy()


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _requests(rng, n, vocab, prompt_max, new_max):
    """Mixed-length (prompt, max_new) pairs; two share a long prefix so
    the prefix cache is exercised."""
    reqs = []
    for _ in range(n):
        plen = int(rng.integers(5, prompt_max))
        reqs.append((rng.integers(0, vocab, size=plen, dtype="int32"),
                     int(rng.integers(4, new_max))))
    shared = reqs[0][0]
    if len(shared) > 4:
        reqs[1] = (shared.copy(), reqs[1][1])
    return reqs


def _requests_with_a_page_shared(rng, n, vocab, prompt_max, new_max,
                                 page_len):
    """``_requests`` whose first prompt holds a whole page, and whose
    last request — admitted once a slot is free, after the first has
    published — opens with that page: a prefix hit on whole pages."""
    import numpy as np
    reqs = _requests(rng, n, vocab, prompt_max, new_max)
    first = rng.integers(0, vocab, size=page_len + 70, dtype="int32")
    reqs[0] = (first, reqs[0][1])
    reqs[-1] = (np.concatenate([first[:page_len], rng.integers(
        0, vocab, size=20, dtype="int32")]), reqs[-1][1])
    return reqs


def _check_pool_stays_in_place(srv, label):
    """The paged decode program as the chip's compiler built it: the
    donated page pool is its output buffer (``alias_bytes``) and its
    scratch (``temp_bytes``) is smaller than one layer's K pages — so no
    layer's slice of the pool, let alone the pool, is copied anywhere.
    On-chip twin of tests/unit/test_serving_paging.py's structure test:
    only here are the layouts real. A pool split over a model axis is
    left to that test (the registry re-lowers from unsharded shapes)."""
    import jax
    from deepspeed_tpu.observability.programs import get_program_registry

    from deepspeed_tpu.inference.cache import kv_leaves
    kv = kv_leaves(srv._paged.pool)
    if any(len(x.sharding.device_set) > 1 for x in kv):
        return
    mem = get_program_registry().get("serving/paged_decode").analyze() or {}
    # (a model with recurrent state keeps it in the pool's tree: the
    # slots' and the pages', aliased like the K/V)
    pool_bytes = srv._paged.pool_bytes() + srv._paged.state_bytes()
    layer_slice = kv[0].nbytes // (kv[0].shape[0] if kv[0].ndim == 5 else 1)
    _say(f"{label}: serving/paged_decode temp_bytes "
         f"{mem.get('temp_bytes')}, alias_bytes {mem.get('alias_bytes')}; "
         f"pool {pool_bytes} bytes, one layer's K pages {layer_slice}")
    _check(mem.get("alias_bytes", 0) >= pool_bytes,
           f"{label}: the paged decode program's outputs alias "
           f"{mem.get('alias_bytes')} bytes of its arguments, less than "
           f"the pool's {pool_bytes}: a pool leaf is not updated in place")
    _check(mem.get("temp_bytes", pool_bytes) < layer_slice,
           f"{label}: the paged decode program needs "
           f"{mem.get('temp_bytes')} bytes of scratch, at least one "
           f"layer's K pages ({layer_slice}): part of the pool is copied")


def _admit_alone(srv, prompt, new, page_len, label):
    """One prompt of four pages and a little into an empty server with
    the default ``prefill_chunk``: nothing decodes, so it goes in as one
    chunk of four pages and one of one, which the counters say."""
    from deepspeed_tpu.observability.metrics import get_registry
    counters = [get_registry().counter("serving/" + name)
                for name in ("prefill_chunk_pages", "prefill_chunks")]
    before = [c.value for c in counters]
    _check(not srv.busy, f"{label}: the server is not empty")
    handle = srv.submit(prompt, max_new_tokens=new)
    srv.run()
    pages, chunks = (c.value - b for c, b in zip(counters, before))
    _say(f"{label}: {len(prompt)} tokens admitted alone went in as "
         f"{chunks} chunk programs of {pages} pages")
    _check((pages, chunks) == (-(-len(prompt) // page_len), 2),
           f"{label}: no chunk of four pages ran: {pages} pages in "
           f"{chunks} chunks")
    return handle


def _check_iteration_log(srv, prompt, written_before, label):
    """The server's log of its host loop (``serving/iterations``), on
    the chip: one more request driven by hand, with a pause before each
    ``advance()`` so that the dispatch in flight has finished before it
    is read. The rows' phases must sum to the wall time a clock of this
    function's own takes from the first call to the last, to 0.1%; a
    read-back must have found its arrays ready (here) and one not (the
    run above, where the host runs ahead of the device)."""
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.serving.metrics import (CALLER, COMPILES, EMPTY, GC,
                                               IN_ADVANCE, READBACK, READY)
    table = get_registry().table("serving/iterations")
    first = table.count
    srv.submit(prompt, max_new_tokens=4)
    t0 = None
    while srv.busy:
        time.sleep(0.05)
        t0 = t0 or time.perf_counter_ns()
        srv.advance()
    wall = time.perf_counter_ns() - t0
    rows = table.read()[written_before - table.count:]
    by_hand = rows[first - table.count:]
    inside = sum(sum(r[IN_ADVANCE]) for r in by_hand)
    between = sum(r[CALLER] + r[EMPTY] for r in by_hand[1:])
    _check(abs(inside + between - wall) <= wall / 1000,
           f"{label}: {len(by_hand)} rows of serving/iterations hold "
           f"{inside} ns inside advance() and {between} ns between, the "
           f"loop from its first advance() to its last took {wall} ns")
    read = [r for r in rows if r[READBACK] > 0]
    ready = sum(r[READY] for r in read)
    _check(0 < ready < len(read) and any(r[READY] for r in by_hand),
           f"{label}: of {len(read)} iterations that read tokens back, "
           f"{ready} found them ready: both kinds should occur")
    whole = sum(sum(r[IN_ADVANCE]) + r[CALLER] + r[EMPTY] for r in rows)
    shares = ", ".join(
        f"{name} {100.0 * sum(r[at] for r in rows) / whole:.2f}%"
        for name, at in (("caller", CALLER), ("empty", EMPTY), ("gc", GC)))
    _say(f"{label}: serving/iterations wrote {len(rows)} rows over "
         f"{whole / 1e9:.2f}s: {shares}; {ready} of {len(read)} read-backs "
         f"found their arrays ready; "
         f"{sum(r[COMPILES] > 0 for r in rows)} rows compiled")


def _first_divergence_gap(a, b, row_of):
    """Two token sequences of one request: 0.0 when they agree, else the
    gap between the two candidates' reference logits where they first
    part (``row_of(j)``: the reference's row that predicts token j)."""
    import numpy as np
    diff = np.nonzero(np.asarray(a) != np.asarray(b))[0]
    if diff.size == 0:
        return 0.0
    j = int(diff[0])
    row = row_of(j)
    return abs(float(row[a[j]] - row[b[j]]))


def _serve_and_check(eng, module, params, reqs, num_slots, max_len,
                     page_len, paging_kernel, logit_tol, label,
                     against_generate=True, reference_logits=None,
                     kernel="paged_attention", iteration_log=False,
                     paging=None):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import tuning

    from deepspeed_tpu.observability.metrics import get_registry

    tuning.clear_last_dispatch()
    options = {"num_slots": num_slots, "max_len": max_len,
               "paging": {"page_len": page_len, "kernel": paging_kernel,
                          **(paging or {})}}
    srv = eng.serve(options)
    rows_before = get_registry().table("serving/iterations").count
    stream = []
    handles = [srv.submit(p, max_new_tokens=m,
                          on_token=lambda r, tok, _s=stream:
                          _s.append(r.request_id))
               for p, m in reqs]
    t0 = time.monotonic()
    srv.run()
    wall = time.monotonic() - t0
    bad = [(h.request_id, h.status) for h in handles
           if h.status != "finished"]
    _check(not bad, f"{label}: requests not finished (shed/timeout): {bad}")
    for h, (_, m) in zip(handles, reqs):
        _check(len(h.output_tokens) == m,
               f"{label}: request {h.request_id} produced "
               f"{len(h.output_tokens)} of {m} tokens")
    # continuous batching: some request's tokens are split by another's
    runs = sum(1 for a, b in zip(stream, stream[1:]) if a != b) + 1
    _check(runs > len(handles),
           f"{label}: streamed tokens never interleaved ({runs} runs over "
           f"{len(handles)} requests)")
    # a wide prefill chunk on the chip, and the same prompt a page at a
    # time: both are held to the reference below with the mix's requests
    long = (np.random.default_rng(len(reqs)).integers(
        0, module.config.vocab_size, size=4 * page_len + page_len // 4,
        dtype="int32"), 8)
    handles.append(_admit_alone(srv, *long, page_len, label))
    reqs = reqs + [long]
    path = tuning.last_dispatch("paged_decode").get("path")
    _mosaic(path, f"{label} paged decode path")
    kern = tuning.last_dispatch(kernel).get(f"page{page_len}")
    _mosaic(kern, f"{label} {kernel} kernel")
    _say(f"{label}: {len(handles)} requests over {num_slots} slots finished "
         f"in {wall:.2f}s (compiles included); paged kernel {kern}")
    _check_pool_stays_in_place(srv, label)
    if iteration_log:
        # (one request more: a family's phase that reconciles its own
        # counters with the requests it sent leaves this out)
        _check_iteration_log(srv, reqs[0][0], rows_before, label)
    srv.close()
    paged = eng.serve(dict(options, paging=dict(options["paging"],
                                                prefill_chunk=page_len)))
    by_page = paged.submit(long[0], max_new_tokens=long[1])
    paged.run()
    paged.close()
    went = paged.metrics
    _check(by_page.status == "finished" and went.prefill_chunks
           == went.prefill_chunk_pages == -(-len(long[0]) // page_len),
           f"{label}: the one-page run did not go a page a chunk")

    # logit-level check against the float32 reference: teacher-force every
    # served sequence through a plain float32 forward of the same weights;
    # each served token must sit within ``logit_tol`` of that position's
    # best reference logit (a random-init greedy chain has near-ties, so
    # the token may differ from the reference argmax, but not by more).
    plain = {"dtype": jnp.float32, "param_dtype": jnp.float32}
    if hasattr(module.config, "attn_backend"):
        plain["attn_backend"] = "reference"
    ref_model = type(module)(dataclasses.replace(module.config, **plain))
    params32 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    width = max(len(p) + m for p, m in reqs)
    full = np.zeros((len(reqs), width), np.int32)
    for i, (h, (p, m)) in enumerate(zip(handles, reqs)):
        full[i, :len(p) + m] = np.concatenate([p, h.output_tokens])
    if reference_logits is None:
        reference_logits = jax.jit(
            lambda prm, ids: ref_model.apply({"params": prm}, ids))
    ref_logits = np.asarray(reference_logits(params32, jnp.asarray(full)))
    # the tolerance is stated in units of the reference logits' spread:
    # a wrong attention path moves a token's logit by whole sigmas, bf16
    # rounding by hundredths of one
    sigma = float(ref_logits.std())
    logit_tol = logit_tol * sigma
    worst, exact, total = 0.0, 0, 0
    for i, (h, (p, m)) in enumerate(zip(handles, reqs)):
        for j, tok in enumerate(h.output_tokens):
            row = ref_logits[i, len(p) + j - 1]
            gap = float(row.max() - row[tok])
            worst = max(worst, gap)
            exact += int(gap == 0.0)
            total += 1
    _say(f"{label}: {exact}/{total} served tokens are the float32 argmax; "
         f"largest logit gap {worst:.4f} = {worst / sigma:.3f} sigma "
         f"(tolerance {logit_tol:.4f} = {logit_tol / sigma:.2f} sigma)")
    _check(worst <= logit_tol,
           f"{label}: a served token is {worst:.4f} below the float32 "
           f"reference's best logit (tolerance {logit_tol:.4f})")
    gap = _first_divergence_gap(
        handles[-1].output_tokens, by_page.output_tokens,
        lambda j: ref_logits[len(reqs) - 1, len(long[0]) + j - 1])
    _say(f"{label}: the prompt prefilled in a chunk of four pages and a "
         f"page at a time gives " + ("the same tokens" if gap == 0.0 else
         f"tokens that part at a near-tie of {gap:.4f}"))
    _check(gap <= logit_tol,
           f"{label}: wide and one-page prefill diverge by a reference "
           f"logit gap of {gap:.4f} > {logit_tol:.4f}")

    # every request that was prefilled: the mix, the long prompt, and
    # the long prompt again a page at a time
    served = reqs + [long]
    if not against_generate:
        return served
    # agreement with generate() (the contiguous-cache one-shot path),
    # reported as a count; a mismatch is a failure only when the two
    # candidates' reference logits differ by more than the tolerance
    lens = np.asarray([len(p) for p, _ in reqs], np.int32)
    pw = int(lens.max())
    padded = np.zeros((len(reqs), pw), np.int32)
    for i, (p, _) in enumerate(reqs):
        padded[i, :len(p)] = p
    new_max = max(m for _, m in reqs)
    gen = np.asarray(eng.generate(padded, max_new_tokens=new_max,
                                  prompt_lengths=lens))
    agree = 0
    for i, (h, (p, m)) in enumerate(zip(handles, reqs)):
        gap = _first_divergence_gap(
            gen[i, len(p):len(p) + m], h.output_tokens,
            lambda j: ref_logits[i, len(p) + j - 1])
        agree += int(gap == 0.0)
        _check(gap <= logit_tol,
               f"{label}: request {i} diverges from generate() by a "
               f"reference logit gap of {gap:.4f} > {logit_tol:.4f}")
    _say(f"{label}: {agree}/{len(reqs)} requests token-identical to "
         f"generate(); the rest diverge at a near-tie inside the tolerance")
    return served


def phase_serve(preset, num_slots, max_len, page_len, n_requests,
                prompt_max, new_max, paging_kernel, n_layers, logit_tol):
    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu as ds

    model = _gpt(preset, n_layers, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, scan_layers=True,
                 max_seq_len=max(max_len, 128))
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params, dtype=jnp.bfloat16)
    reqs = _requests(np.random.default_rng(1), n_requests,
                     model.config.vocab_size, prompt_max, new_max)
    _check(n_requests > num_slots, "serve needs more requests than slots")
    _serve_and_check(eng, model, params, reqs, num_slots, max_len, page_len,
                     paging_kernel, logit_tol, f"serve {preset}",
                     iteration_log=True)


def phase_olmoe(n_layers, num_slots, max_len, page_len, n_requests,
                prompt_max, new_max, paging_kernel, logit_tol, **widths):
    """OLMoE's block at published widths through the same serving path:
    the chunk-prefill and paged-decode programs carry an expert layer,
    the grouped matmul is a Mosaic call in the compiled decode program,
    and the router's counts come back with the tokens."""
    import numpy as np
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.observability.programs import get_program_registry

    model = OLMoE(OLMoEConfig(num_hidden_layers=n_layers, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16, **widths))
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params, dtype=jnp.bfloat16)
    reqs = _requests(np.random.default_rng(2), n_requests,
                     model.config.vocab_size, prompt_max, new_max)
    counters = {name: get_registry().counter("moe/" + name)
                for name in ("assignments", "expert_calls",
                             "experts_touched", "experts_offered")}
    before = {name: c.value for name, c in counters.items()}
    traced = _grouped_matmul_traced()
    reqs = _serve_and_check(eng, model, params, reqs, num_slots, max_len,
                            page_len, paging_kernel, logit_tol,
                            "serve olmoe")
    _grouped_matmul_engaged("serve olmoe",
                            n_layers * model.config.num_experts, traced)
    decode = get_program_registry().get("serving/paged_decode")
    args, kwargs = decode._last_avals
    hlo = decode.lower(*args, **kwargs).compile().as_text()
    _check("ragged-dot" in hlo and "tpu_custom_call" in hlo,
           "serve olmoe: the compiled decode program holds no Mosaic "
           "ragged-dot call: the grouped expert matmul did not lower to "
           "a kernel of that family")
    moved = {name: c.value - before[name] for name, c in counters.items()}
    _say(f"serve olmoe: router counted {moved}")
    k, experts = model.config.num_experts_per_tok, model.config.num_experts
    _check(moved["expert_calls"] > 0
           and moved["experts_offered"] == moved["expert_calls"] * experts,
           f"serve olmoe: no routing was counted: {moved}")
    # every generated token but a request's last is routed once a layer,
    # and so is every prompt token the prefix cache did not serve
    least = sum(m - 1 for _, m in reqs) * k * n_layers
    most = sum(len(p) + m - 1 for p, m in reqs) * k * n_layers
    _check(least < moved["assignments"] <= most,
           f"serve olmoe: {moved['assignments']} token-expert pairs, "
           f"outside ({least}, {most}]: idle slots or padding were routed")


def phase_lfm2(n_layers, num_slots, max_len, page_len, n_requests,
               prompt_max, new_max, paging_kernel, logit_tol):
    """LFM2-24B-A2B's first layers at published widths through the same
    serving path: the chunk-prefill and paged-decode programs carry the
    convolution state beside the K/V pages (the pool stays where it is,
    state included: ``_check_pool_stays_in_place``), the paged kernel
    computes four query heads a K/V head, and a request admitted on a
    shared page starts from the state stored with it."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lfm2 import LFM2, LFM2Config
    from deepspeed_tpu.observability.metrics import get_registry

    layers = LFM2Config().layer_types[:n_layers]
    model = LFM2(LFM2Config(num_hidden_layers=n_layers, layer_types=layers,
                            max_position_embeddings=max(max_len, 128),
                            dtype=jnp.float32, param_dtype=jnp.bfloat16))
    # float32 activations over the bf16 weights, as the benchmark's cell
    # runs it: in bf16 a normalised top-4 router's near-ties flip and a
    # served token leaves the reference by more than the tolerance
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    vocab = model.config.vocab_size
    reqs = _requests_with_a_page_shared(rng, n_requests, vocab, prompt_max,
                                        new_max, page_len)
    names = ("serving/prefill_tokens_reused",
             "serving/state_snapshots_restored", "serving/state_resets",
             "serving/state_snapshots_stored", "moe/expert_calls")
    counters = {name: get_registry().counter(name) for name in names}
    before = {name: c.value for name, c in counters.items()}
    traced = _grouped_matmul_traced()
    # ragged generate() refuses such a model (a row's padding would write
    # state): the served tokens are held to the float32 reference alone —
    # the family's plain one, every expert computed by XLA's own products
    # at full precision: the module itself with float32 weights is no
    # reference on the chip, where the grouped matmul multiplies float32
    # by float32 in one bf16 pass (0.86 of a logit under the best)
    from benchmarks.chip.families import lfm2 as family
    cfg = model.config
    sizes = {k: getattr(cfg, k) for k in family.SIZE_KEYS}
    published = {"norm_eps": cfg.norm_eps, "use_expert_bias": True,
                 "norm_topk_prob": cfg.norm_topk_prob,
                 "routed_scaling_factor": cfg.routed_scaling_factor,
                 "rope_parameters": {"rope_theta": cfg.rope_theta}}

    @jax.jit
    def reference_logits(prm, ids):
        with jax.default_matmul_precision("highest"):
            return family.reference_logits(prm, ids, sizes, published,
                                           near_ties="kept")

    served = _serve_and_check(eng, model, params, reqs, num_slots, max_len,
                              page_len, paging_kernel, logit_tol,
                              "serve lfm2", against_generate=False,
                              reference_logits=reference_logits)
    _grouped_matmul_engaged(
        "serve lfm2", model.config.num_moe_layers * model.config.num_experts,
        traced)
    moved = {name: c.value - before[name] for name, c in counters.items()}
    _say(f"serve lfm2: counted {moved}")
    _check(moved["serving/prefill_tokens_reused"] >= page_len
           and moved["serving/state_snapshots_restored"] >= 1,
           f"serve lfm2: no prefix hit restored a state: {moved}")
    _check(moved["serving/state_snapshots_restored"]
           + moved["serving/state_resets"] == len(served)
           and moved["serving/state_snapshots_stored"] >= 1,
           f"serve lfm2: admissions and stored states do not add up: {moved}")
    _check(moved["moe/expert_calls"] > 0
           and moved["moe/expert_calls"] % (n_layers - 2) == 0,
           f"serve lfm2: the expert layers' calls were not counted: {moved}")
    try:
        eng.generate(np.zeros((2, 8), np.int32), max_new_tokens=2,
                     prompt_lengths=np.asarray([8, 5], np.int32))
    except NotImplementedError as e:
        _say(f"serve lfm2: ragged generate() refused: {e}")
    else:
        _check(False, "serve lfm2: ragged generate() did not refuse a "
                      "model with recurrent state")


def phase_falcon_h1(n_layers, num_slots, max_len, page_len, n_requests,
                    prompt_max, new_max, paging_kernel, logit_tol):
    """Falcon-H1-34B's block at published widths through the same serving
    path, in bf16 as its cell runs it: the chunk-prefill and paged-decode
    programs carry the mixer's two states beside the K/V pages (the pool
    stays where it is, the slots' matrix states included:
    ``_check_pool_stays_in_place``), the state update is the Mosaic kernel
    of ``ops/pallas/ssm_update.py``, and a request admitted on a shared
    page starts from the snapshot at that page's end."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.falcon_h1 import FalconH1, FalconH1Config
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.ops.pallas import tuning
    from benchmarks.chip import manifest
    from benchmarks.chip.families import falcon_h1 as family

    # the multipliers are the published ones, from the cell's file
    published = manifest.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "chip",
        "configs", "falcon-h1-34b-9l-serve.json"))
    model = FalconH1(FalconH1Config(
        num_hidden_layers=n_layers, vocab_size=published["vocab_size"],
        max_position_embeddings=max(max_len, 128),
        **{k: published[k] for k in family.MULTIPLIER_KEYS},
        rope_theta=float(published["rope_theta"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params, dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    reqs = _requests_with_a_page_shared(rng, n_requests,
                                        model.config.vocab_size, prompt_max,
                                        new_max, page_len)
    names = ("serving/prefill_tokens_reused",
             "serving/state_snapshots_restored", "serving/state_resets",
             "serving/state_snapshots_taken", "serving/state_restore_missed")
    counters = {name: get_registry().counter(name) for name in names}
    before = {name: c.value for name, c in counters.items()}
    sizes = dict(family.sizes(published, False), num_hidden_layers=n_layers)

    @jax.jit
    def reference_logits(prm, ids):
        with jax.default_matmul_precision("highest"):
            return family.reference_logits(prm, ids, sizes, published)

    served = _serve_and_check(eng, model, params, reqs, num_slots, max_len,
                              page_len, paging_kernel, logit_tol,
                              "serve falcon_h1", against_generate=False,
                              reference_logits=reference_logits,
                              paging={"state_snapshots": 2 * num_slots})
    update, = tuning.last_dispatch("ssm_update").values()
    _mosaic(update, "serve falcon_h1 state update")
    _check(update.get("impl") == "kernel",
           f"serve falcon_h1: the state update is not the kernel: {update}")
    moved = {name: c.value - before[name] for name, c in counters.items()}
    _say(f"serve falcon_h1: counted {moved}")
    _check(moved["serving/prefill_tokens_reused"] >= page_len
           and moved["serving/state_snapshots_restored"] >= 1,
           f"serve falcon_h1: no prefix hit restored a snapshot: {moved}")
    _check(moved["serving/state_snapshots_restored"]
           + moved["serving/state_resets"] == len(served)
           and moved["serving/state_snapshots_taken"] >= 2,
           f"serve falcon_h1: admissions and snapshots do not add up: "
           f"{moved}")
    try:
        eng.generate(np.zeros((2, 8), np.int32), max_new_tokens=2,
                     prompt_lengths=np.asarray([8, 5], np.int32))
    except NotImplementedError as e:
        _say(f"serve falcon_h1: ragged generate() refused: {e}")
    else:
        _check(False, "serve falcon_h1: ragged generate() did not refuse a "
                      "model with recurrent state")


def phase_phi4flash(n_layers, num_slots, max_len, page_len, n_requests,
                    prompt_max, new_max, paging_kernel, logit_tol):
    """Phi-4-mini-flash's layers at published widths through the same
    serving path, as its cell runs it (float32 activations over bf16
    weights and a bf16 K/V cache): the decode program holds
    the paged kernel at ten cached heads of 128 (two halves of 64
    stacked, four query rows a head) for the full layer and for the
    cross layer that reads the same pages, and on the window layers'
    rings the write (``%ring_append``) and the contiguous decode kernel;
    the pool — pages, rings, states — stays where it is; and what the
    rings cannot serve is refused."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.observability.programs import get_program_registry
    from deepspeed_tpu.ops.pallas import tuning
    from benchmarks.chip import manifest
    from benchmarks.chip.families import phi4flash as family

    published = manifest.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "chip",
        "configs", "phi-4-mini-flash-serve.json"))
    config = dict(published, num_hidden_layers=n_layers,
                  vocab_size=published["vocab_size"] // 8,
                  max_position_embeddings=max(max_len, 128))
    model = family.build(config, False)
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params,
                            dtype=getattr(jnp, config["compute_dtype"]))
    reqs = _requests(np.random.default_rng(4), n_requests,
                     model.config.vocab_size, prompt_max, new_max)
    names = ("serving/self_decoder_positions",
             "serving/cross_decoder_positions", "serving/ring_tokens_written")
    counters = {name: get_registry().counter(name) for name in names}
    before = {name: c.value for name, c in counters.items()}
    sizes = family.sizes(config, False)

    @jax.jit
    def reference_logits(prm, ids):
        with jax.default_matmul_precision("highest"):
            return family.reference_logits(prm, ids, sizes, config)

    for option, asked in (("enable_prefix_cache", {"paging": {
            "page_len": page_len, "enable_prefix_cache": True}}),
            ("kv_int8", {"paging": {"page_len": page_len,
                                    "enable_prefix_cache": False},
                         "quantize": {"kv": "int8"}})):
        try:
            eng.serve({"num_slots": num_slots, "max_len": max_len, **asked})
        except NotImplementedError as e:
            _say(f"serve phi4flash: {option} refused: {e}")
        else:
            _check(False, f"serve phi4flash: {option} was not refused")
    # the two decode kernels at the blocks the cell's shapes take from the
    # committed table (64 slots there: five or ten cached heads a grid
    # step since PR 58), under this phase's keys
    window = published["sliding_window"]
    table = tuning.load_artifact(tuning.DEFAULTS_PATH)["entries"]
    blocks = {}
    for kernel, structure, cell_sk, sk in (
            ("paged_attention", f"page{page_len}", 4096, max_len),
            ("decode_attention", "dma", window, window)):
        cell, here = (tuning.make_key(
            kernel, structure, sq=sq, sk=tokens, d=128, dtype=jnp.bfloat16,
            causal=True) for sq, tokens in ((64, cell_sk), (num_slots, sk)))
        blocks[here] = {k: table[cell][k] for k in ("block_k", "head_block")}
    with tuning.tuning_table(blocks):
        _serve_and_check(eng, model, params, reqs, num_slots, max_len,
                         page_len, paging_kernel, logit_tol,
                         "serve phi4flash", against_generate=False,
                         reference_logits=reference_logits,
                         paging={"enable_prefix_cache": False})
    ring, = tuning.last_dispatch("decode_attention").values()
    _mosaic(ring, "serve phi4flash ring kernel")
    paged = tuning.last_dispatch("paged_attention")[f"page{page_len}"]
    for name, rec in (("ring", ring), ("paged", paged)):
        want = blocks[rec["key"]]
        _check((rec["source"], rec["block_k"], rec["head_block"],
                rec["rows"]) == ("runtime", want["block_k"],
                                 want["head_block"], 4 * want["head_block"]),
               f"serve phi4flash: the {name} kernel ran at {rec}, not at "
               f"{want} with four query rows a cached head")
    write, = tuning.last_dispatch("ring_append").values()
    _mosaic(write, "serve phi4flash ring write")
    decode = get_program_registry().get("serving/paged_decode")
    args, kwargs = decode._last_avals
    hlo = decode.lower(*args, **kwargs).compile().as_text()
    kinds = {k: len(family.layers_of(sizes, k))
             for k in ("window_attn", "shared_attn", "cross_attn")}
    # a window layer's write is a call of its own, under its own name
    kinds["ring_append"] = kinds["window_attn"]
    for kind, n in kinds.items():
        calls = sum(1 for line in hlo.splitlines()
                    if f"%{kind}" in line.split(" = ")[0]
                    and "tpu_custom_call" in line)
        _check(calls == n, f"serve phi4flash: {calls} Mosaic calls named "
                           f"%{kind}* in the decode program, not {n}")
    moved = {name: c.value - before[name] for name, c in counters.items()}
    _say(f"serve phi4flash: counted {moved}")
    _check(0 < moved["serving/cross_decoder_positions"] * page_len
           <= moved["serving/self_decoder_positions"],
           f"serve phi4flash: the cross-decoder ran on more than one "
           f"position a chunk: {moved}")
    try:
        eng.generate(np.zeros((2, 8), np.int32), max_new_tokens=2,
                     prompt_lengths=np.asarray([8, 5], np.int32))
    except NotImplementedError as e:
        _say(f"serve phi4flash: ragged generate() refused: {e}")
    else:
        _check(False, "serve phi4flash: ragged generate() did not refuse a "
                      "model with recurrent state")


def phase_kanana(n_layers, num_slots, max_len, page_len, n_requests,
                 prompt_max, new_max, paging_kernel, logit_tol):
    """Kanana-2-30B-A3B's first layers (the DeepSeek-V3 architecture) at
    published widths through the same serving path: the pool keeps one
    compressed vector a token and layer, the decode program walks it with
    the latent kernel (all 32 query heads a step) and leaves it where it
    is, a prefix hit shares latent pages, the router's weights are scaled
    by 2.448 and every live row takes the shared experts once."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.ops.pallas import tuning

    # float32 activations over the bf16 weights, as the benchmark's cell
    # runs it and for LFM2's reason (a normalised sigmoid top-k)
    model = DeepseekV3(DeepseekV3Config(
        num_hidden_layers=n_layers, max_position_embeddings=max(max_len, 128),
        dtype=jnp.float32, param_dtype=jnp.bfloat16))
    params = _seeded_params(model)
    eng = ds.init_inference(model, params=params, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    vocab = model.config.vocab_size
    reqs = _requests_with_a_page_shared(rng, n_requests, vocab, prompt_max,
                                        new_max, page_len)
    names = ("serving/prefill_tokens_reused", "serving/latent_tokens_walked",
             "moe/expert_calls", "moe/assignments", "moe/shared_expert_rows")
    counters = {name: get_registry().counter(name) for name in names}
    before = {name: c.value for name, c in counters.items()}
    traced = _grouped_matmul_traced()
    # generate() refuses a latent cache: the served tokens are held to the
    # family's plain float32 reference alone
    from benchmarks.chip.families import deepseek_v3 as family
    cfg = model.config
    sizes = {k: getattr(cfg, k) for k in family.SIZE_KEYS}
    published = {"rms_norm_eps": cfg.rms_norm_eps,
                 "norm_topk_prob": cfg.norm_topk_prob,
                 "routed_scaling_factor": cfg.routed_scaling_factor,
                 "rope_theta": cfg.rope_theta,
                 "rope_interleave": cfg.rope_interleave}

    @jax.jit
    def reference_logits(prm, ids):
        with jax.default_matmul_precision("highest"):
            return family.reference_logits(prm, ids, sizes, published,
                                           near_ties="kept")

    _serve_and_check(eng, model, params, reqs, num_slots, max_len, page_len,
                     paging_kernel, logit_tol, "serve kanana",
                     against_generate=False,
                     reference_logits=reference_logits,
                     kernel="latent_attention")
    _check(not tuning.last_dispatch("paged_attention"),
           "serve kanana: the K/V kernel was dispatched over a latent pool")
    _grouped_matmul_engaged(
        "serve kanana", cfg.num_moe_layers * cfg.n_routed_experts, traced)
    moved = {name: c.value - before[name] for name, c in counters.items()}
    _say(f"serve kanana: counted {moved}")
    _check(moved["serving/prefill_tokens_reused"] >= page_len,
           f"serve kanana: no prefix hit on latent pages: {moved}")
    _check(moved["serving/latent_tokens_walked"] > 0,
           f"serve kanana: the latent walk was not counted: {moved}")
    moe_layers = n_layers - cfg.first_k_dense_replace
    _check(moved["moe/expert_calls"] > 0
           and moved["moe/expert_calls"] % moe_layers == 0
           and moved["moe/assignments"] == cfg.num_experts_per_tok
           * moved["moe/shared_expert_rows"],
           f"serve kanana: routed and shared rows do not add up: {moved}")
    try:
        eng.generate(np.zeros((2, 8), np.int32), max_new_tokens=2,
                     prompt_lengths=np.asarray([8, 5], np.int32))
    except ValueError as e:
        _say(f"serve kanana: generate() refused: {e}")
    else:
        _check(False, "serve kanana: generate() did not refuse a latent "
                      "cache")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _kernel_checks(seq, heads, batch, cache_len, gemv_k, gemv_n, sparse_seq,
                   gemv_timeout_s):
    """(name, fn) pairs; each fn runs one kernel against its reference."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from deepspeed_tpu.ops.pallas import (decode_attention, flash_attention,
                                          fused_adamw, fused_lamb,
                                          fused_layer_norm, paged_attention,
                                          quantize, dequantize, tuning)
    from deepspeed_tpu.ops.pallas import wo_int8_matmul as wo
    from deepspeed_tpu.ops.transformer.attention import (
        _reference_attention, attention)

    rng = np.random.default_rng(0)

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def flash(d, dropout):
        def run():
            q, k, v = (normal((batch, seq, heads, d)) for _ in range(3))
            kw = (dict(dropout_rate=0.1, dropout_rng=jax.random.PRNGKey(3))
                  if dropout else {})

            def kern(q, k, v):
                return flash_attention(q, k, v, causal=True, **kw)

            def ref(q, k, v):
                return _reference_attention(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True,
                    deterministic=not dropout, **kw)

            def loss(f):
                return lambda q, k, v: jnp.sum(
                    f(q, k, v).astype(jnp.float32) ** 2)

            tuning.clear_last_dispatch()
            fwd = jax.jit(kern)
            _check("tpu_custom_call" in fwd.lower(q, k, v).compile()
                   .as_text(),
                   "flash forward compiled without a Mosaic custom call")
            _close("fwd", fwd(q, k, v), jax.jit(ref)(q, k, v), 0.03)
            got = jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(q, k, v)
            want = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
            for name, g, w in zip("qkv", got, want):
                _close(f"d{name}", g, w, 0.05)
            rec = tuning.last_dispatch("flash_attention")
            for s in rec:
                _mosaic(rec[s], f"flash_attention/{s}")
            return sorted(rec)
        return run

    # the decode kernels' head block (_common.pick_head_block) is 8 for 16
    # heads (16 where a bf16 paged pool's caller or table entry asks for
    # it: PR 51), 4 for GPT-2's 12, 2 / 1 for the 6 / 3 heads one device
    # holds at mp_size 2 / 4, and 10 or 5 of ten bf16 heads where asked
    # (PR 58): each size is its own Mosaic tiling, and a refusal there is
    # a SIGABRT
    def decode(heads, head_block, ask=None, kv_heads=None, d=64):
        def run():
            kv = kv_heads or heads
            q = normal((batch * 2, 1, heads, d))
            k, v = (normal((batch * 2, kv, d, cache_len))
                    for _ in range(2))
            lengths = jnp.asarray(
                rng.integers(1, cache_len, size=batch * 2), jnp.int32)
            from deepspeed_tpu.ops.pallas.decode_attention import \
                _decode_dense
            tuning.clear_last_dispatch()
            kw = {"head_block": ask} if ask else {}
            got = jax.jit(lambda *a: decode_attention(*a, **kw))(
                q, k, v, lengths)
            want = _decode_dense(
                q[:, 0].astype(jnp.float32), k, v, lengths,
                jnp.zeros((heads,), jnp.float32), scale=d ** -0.5,
                alibi=False)
            _close("decode", got[:, 0], want, 0.03)
            rec = tuning.last_dispatch("decode_attention").get("dma")
            _mosaic(rec, "decode_attention")
            _check(rec["head_block"] == head_block,
                   f"{heads} heads ran at head block {rec['head_block']}, "
                   f"not {head_block}")
            _check(rec["rows"] == head_block * heads // kv,
                   f"a grid step of {rec['rows']} query-head rows, not "
                   f"{head_block * heads // kv}")
        return run

    def paged(heads, head_block, int8, dtype=jnp.bfloat16, kv_heads=None,
              ask=None, d=64):
        def run():
            page_len, slots, max_pages = 128, 4, cache_len // 128
            num_pages = slots * max_pages + 1
            kv = kv_heads or heads
            q = normal((slots, 1, heads, d), dtype)
            kp, vp = (normal((num_pages, kv, d, page_len), dtype)
                      for _ in range(2))
            kn, vn = (normal((slots, kv, d, 1), dtype) for _ in range(2))
            ptab = jnp.asarray(
                1 + rng.permutation(num_pages - 1)[:slots * max_pages]
                .reshape(slots, max_pages), jnp.int32)
            lengths = jnp.asarray(
                [0, 1, cache_len // 2 + 3, cache_len - 1][:slots], jnp.int32)
            scales = {}
            if int8:
                from deepspeed_tpu.inference.cache import _quantize_kv
                (kp, ks), (vp, vs) = _quantize_kv(kp), _quantize_kv(vp)
                scales = dict(k_scale=ks, v_scale=vs)
            tuning.clear_last_dispatch()
            got = jax.jit(lambda *a: paged_attention(
                *a, head_block=ask, **scales))(
                q, kp, vp, ptab, lengths, kn, vn)
            rec = tuning.last_dispatch("paged_attention").get(
                f"page{page_len}")
            _mosaic(rec, "paged_attention")
            _check(rec["head_block"] == head_block,
                   f"{heads} heads ran at head block {rec['head_block']}, "
                   f"not {head_block}")
            _check(rec["rows"] == head_block * heads // kv,
                   f"a grid step of {rec['rows']} query-head rows, not "
                   f"{head_block * heads // kv}")
            # the products run in the pool's own type: a bf16 pool's in
            # bf16, a float32 pool's and an int8 pool's dequantised
            # blocks in float32
            products = "float32" if int8 else jnp.dtype(dtype).name
            _check(rec["products"] == products,
                   f"a {'int8' if int8 else products} pool's products ran in "
                   f"{rec['products']}")
            want = paged_attention(q, kp, vp, ptab, lengths, kn, vn,
                                   impl="dense", **scales)
            _close("paged", got, want, 0.03)
            return f"products in {rec['products']}"
        return run

    def adam_like(make_fused, make_optax, tol):
        def run():
            params = {"w": normal((768, 3072), jnp.float32, 0.02),
                      "odd": normal((50257, 3), jnp.float32, 0.02),
                      "b": normal((768,), jnp.float32, 0.02)}
            grads = jax.tree.map(
                lambda p: normal(p.shape, jnp.float32, 0.01), params)
            outs = []
            for tx in (make_fused(), make_optax()):
                @jax.jit
                def two_steps(params, grads, tx=tx):
                    state = tx.init(params)
                    for _ in range(2):
                        upd, state = tx.update(grads, state, params)
                        params = optax.apply_updates(params, upd)
                    return params
                outs.append(two_steps(params, grads))
            for name in params:
                _close(name, outs[0][name] - params[name],
                       outs[1][name] - params[name], tol)
        return run

    def layernorm():
        for rows, d in ((4096, 768), (512, 4096)):
            x = normal((rows, d), jnp.bfloat16)
            g, b = normal((d,), jnp.float32), normal((d,), jnp.float32)

            def ref(x, g, b):
                x32 = x.astype(jnp.float32)
                mu = x32.mean(-1, keepdims=True)
                var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
                return ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * g + b
                        ).astype(x.dtype)

            _close(f"ln{d}", jax.jit(fused_layer_norm)(x, g, b),
                   ref(x, g, b), 0.02)
            loss = lambda f: (lambda x, g, b: jnp.sum(
                f(x, g, b).astype(jnp.float32) ** 2))
            got = jax.jit(jax.grad(loss(fused_layer_norm),
                                   argnums=(0, 1, 2)))(x, g, b)
            want = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(x, g, b)
            for name, a, w in zip(("dx", "dgamma", "dbeta"), got, want):
                _close(f"ln{d}/{name}", a, w, 0.03)

    def quantizer():
        x = normal((64, 4096), jnp.float32)
        q, s = jax.jit(lambda x: quantize(x, groups=64))(x)
        _close("sym", dequantize(q, s), x, 1.0 / 127)
        q, s, zp = jax.jit(
            lambda x: quantize(x, groups=64, asymmetric=True))(x)
        _close("asym", dequantize(q, s, zp), x, 1.0 / 127)
        # stochastic rounding: the pltpu.prng_* kernel body. Every code is
        # floor or floor+1 of the scaled value, the rounding is unbiased,
        # and it is not plain round-to-nearest.
        q, s = jax.jit(
            lambda x: quantize(x, groups=64, stochastic=True, seed=7))(x)
        scaled = np.asarray(x) / np.asarray(s)[:, None]
        qn = np.asarray(q, np.float32)
        _check(((qn == np.floor(scaled)) | (qn == np.floor(scaled) + 1)
                | (np.abs(scaled) > 127)).all(),
               "stochastic codes are not floor/floor+1 of the scaled input")
        bias = float(np.mean(qn - scaled))
        _check(abs(bias) < 0.01, f"stochastic rounding is biased: {bias}")
        _check((qn != np.round(scaled)).mean() > 0.05,
               "stochastic rounding equals round-to-nearest")

    def int8_weight(n, k=gemv_k):
        x1 = normal((1, k))
        xm = normal((256, k))
        q = jnp.asarray(rng.integers(-127, 128, size=(k, n),
                                     dtype=np.int8))
        s = jnp.asarray(np.abs(rng.standard_normal((1, n))) * 0.01,
                        jnp.float32)
        ref = lambda x: jnp.dot(
            x.astype(jnp.float32), q.astype(jnp.float32) * s)
        return x1, xm, q, s, ref

    def _gbps(fn, *args, nbytes):
        jax.block_until_ready(fn(*args))
        t0 = time.monotonic()
        for _ in range(20):
            out = fn(*args)
        jax.block_until_ready(out)
        return nbytes / ((time.monotonic() - t0) / 20) / 1e9

    def int8_mxu():
        x1, xm, q, s, ref = int8_weight(gemv_n)
        f = jax.jit(lambda x: wo.wo_int8_matmul(x, q, s))
        tuning.clear_last_dispatch()
        _close("m=1", f(x1), ref(x1), 0.02)
        _close("m=256", f(xm), ref(xm), 0.02)
        rec = tuning.last_dispatch("wo_int8_matmul")
        _check(rec["m1"]["impl"] == "mxu" and rec["mN"]["impl"] == "mxu",
               f"int8 matmul did not take the MXU kernel: {rec}")
        return (f"m=1 weight read {_gbps(f, x1, nbytes=q.size):.0f} GB/s "
                "(smoke reading)")

    def int8_gemv():
        # the comment on the kernel records a variant that hung the
        # backend: a hang here must fail the phase, not the machine
        with _deadline(gemv_timeout_s):
            x1, _, q, s, ref = int8_weight(gemv_n)
            f = jax.jit(lambda x: wo._wo_int8_gemv(
                x, q, s.reshape(-1), wo.GEMV_BLOCK_N, wo.GEMV_BLOCK_K,
                jnp.bfloat16))
            _close("gemv", f(x1), ref(x1), 0.02)
            return (f"m=1 weight read {_gbps(f, x1, nbytes=q.size):.0f} GB/s "
                    "(smoke reading)")

    def int8_ragged_vocab():
        # vocab 50257 has no 128-aligned tiling: the dispatch must SAY it
        # took the jnp dequant path, never take it quietly
        x1, _, q, s, ref = int8_weight(50257, k=min(gemv_k, 768))
        tuning.clear_last_dispatch()
        _close("vocab", jax.jit(
            lambda x: wo.wo_int8_matmul(x, q, s))(x1), ref(x1), 0.02)
        rec = tuning.last_dispatch("wo_int8_matmul")["m1"]
        _check(rec["impl"] == "dense" and rec["reason"],
               f"ragged-vocab int8 matmul left no dense+reason record: {rec}")

    def head_dim_80():
        # gpt2-2.7b: auto keeps the reference path and says why
        q, k, v = (normal((1, 256, 4, 80)) for _ in range(3))
        tuning.clear_last_dispatch()
        out = jax.jit(lambda q, k, v: attention(
            q, k, v, causal=True, seq_parallel="none"))(q, k, v)
        _check(bool(jnp.isfinite(out.astype(jnp.float32)).all()),
               "d=80 attention produced non-finite values")
        rec = tuning.last_dispatch("attention")["backend"]
        _check(rec["backend"] == "reference"
               and "head_dim 80" in (rec["reason"] or ""),
               f"d=80 auto dispatch not recorded as reference+reason: {rec}")

    def block_sparse():
        from deepspeed_tpu.ops.sparse_attention import (
            BSLongformerSparsityConfig, sparse_attention)
        h = 8
        cfg = BSLongformerSparsityConfig(
            num_heads=h, block=16, num_sliding_window_blocks=8,
            global_block_indices=[0])
        q, k, v = (normal((batch, sparse_seq, h, 64)) for _ in range(3))

        def loss(backend):
            return lambda q, k, v: jnp.sum(sparse_attention(
                q.astype(jnp.float32) if backend == "dense" else q,
                k.astype(jnp.float32) if backend == "dense" else k,
                v.astype(jnp.float32) if backend == "dense" else v,
                cfg, backend=backend).astype(jnp.float32) ** 2)

        fwd = jax.jit(lambda q, k, v: sparse_attention(
            q, k, v, cfg, backend="pallas"))
        _check("tpu_custom_call" in fwd.lower(q, k, v).compile().as_text(),
               "block-sparse forward has no Mosaic call")
        want = jax.jit(lambda q, k, v: sparse_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), cfg, backend="dense"))(q, k, v)
        _close("fwd", fwd(q, k, v), want, 0.03)
        got = jax.jit(jax.grad(loss("pallas"), argnums=(0, 1, 2)))(q, k, v)
        ref = jax.jit(jax.grad(loss("dense"), argnums=(0, 1, 2)))(q, k, v)
        for name, g, w in zip("qkv", got, ref):
            _close(f"d{name}", g, w, 0.05)

    return [
        ("flash d=64", flash(64, False)),
        ("flash d=64 dropout", flash(64, True)),
        ("flash d=128", flash(128, False)),
        ("flash d=128 dropout", flash(128, True)),
        ("attention d=80 (auto -> reference)", head_dim_80),
        (f"decode_attention {heads} heads", decode(heads, 4)),
        ("decode_attention 16 heads (head block 8)", decode(16, 8)),
        ("decode_attention 16 heads (head block 16, asked for)",
         decode(16, 16, ask=16)),
        ("decode_attention 6 heads (head block 2)", decode(6, 2)),
        ("decode_attention 3 heads (head block 1)", decode(3, 1)),
        # ten cached heads of 128 with four query rows each over a bf16
        # cache (Phi-4-mini-flash, PR 58): ten or five a grid step where
        # asked, 40 or 20 rows; two, the constant's answer, otherwise
        ("decode_attention 40 heads on 10 (head block 10, asked for)",
         decode(40, 10, ask=10, kv_heads=10, d=128)),
        ("decode_attention 40 heads on 10 (head block 5, asked for)",
         decode(40, 5, ask=5, kv_heads=10, d=128)),
        ("decode_attention 40 heads on 10 (head block 2)",
         decode(40, 2, kv_heads=10, d=128)),
        (f"paged_attention bf16 pages {heads} heads",
         paged(heads, 4, False)),
        ("paged_attention bf16 pages 16 heads (head block 8)",
         paged(16, 8, False)),
        # ... and all sixteen in one grid step where that is asked for, as
        # the three sixteen-head cells' entry of the tuning table asks
        ("paged_attention bf16 pages 16 heads (head block 16, asked for)",
         paged(16, 16, False, ask=16)),
        ("paged_attention bf16 pages 6 heads (head block 2)",
         paged(6, 2, False)),
        ("paged_attention bf16 pages 3 heads (head block 1)",
         paged(3, 1, False)),
        ("paged_attention float32 pages 32 heads on 8 (head block 2)",
         paged(32, 2, False, jnp.float32, kv_heads=8)),
        # a bf16 pool's step is not held to eight rows (PR 49): all four
        # K/V heads and their twenty query heads
        ("paged_attention bf16 pages 20 heads on 4 (head block 4, 20 rows)",
         paged(20, 4, False, kv_heads=4)),
        ("paged_attention bf16 pages 40 heads on 10 (head block 10, asked "
         "for, 40 rows)", paged(40, 10, False, kv_heads=10, ask=10, d=128)),
        ("paged_attention bf16 pages 40 heads on 10 (head block 5, asked "
         "for, 20 rows)", paged(40, 5, False, kv_heads=10, ask=5, d=128)),
        ("paged_attention float32 pages 40 heads on 10 (asked for 10: head "
         "block 2)", paged(40, 2, False, jnp.float32, kv_heads=10, ask=10,
                          d=128)),
        (f"paged_attention int8 pages {heads} heads", paged(heads, 4, True)),
        ("paged_attention int8 pages 3 heads (head block 1)",
         paged(3, 1, True)),
        ("fused_adam", adam_like(lambda: fused_adamw(1e-3, weight_decay=0.01),
                                 lambda: optax.adamw(1e-3, weight_decay=0.01),
                                 1e-4)),
        ("fused_lamb", adam_like(lambda: fused_lamb(1e-3, weight_decay=0.01),
                                 lambda: optax.lamb(1e-3, weight_decay=0.01),
                                 1e-3)),
        ("layernorm", layernorm),
        ("quantizer (sym, asym, prng stochastic)", quantizer),
        ("wo_int8_matmul MXU", int8_mxu),
        ("wo_int8_matmul m=1 GEMV", int8_gemv),
        ("wo_int8_matmul vocab 50257 (-> recorded dense)",
         int8_ragged_vocab),
        ("block_sparse fwd+bwd", block_sparse),
    ]


def phase_kernels(**sizes):
    """Every kernel runs; the phase fails with the full list of refusals
    (one chip call should show them all)."""
    failed = []
    for name, fn in _kernel_checks(**sizes):
        t0 = time.monotonic()
        try:
            note = fn()
        except Exception:
            failed.append(name)
            _say(f"kernel {name}: FAILED\n{traceback.format_exc()}")
        else:
            _say(f"kernel {name}: ok in {time.monotonic() - t0:.1f}s"
                 + (f" — {note}" if note else ""))
    _check(not failed, f"kernels failed: {failed}")


# ---------------------------------------------------------------------------
# offload
# ---------------------------------------------------------------------------

def phase_offload(preset, n_layers, seq, micro):
    """An offload config must move state to host memory, or refuse by
    name: streamed host Adam (``offload_optimizer``) trains with its
    moments in ``pinned_host``; ZeRO-Inference (``init_inference(
    offload_params=True)``) decodes with its block kernels there; the
    training-side ``offload_param`` raises its named error on TPU."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.runtime.engine import ParamOffloadUnsupportedError

    def build(zero):
        return _trainer(preset, n_layers, seq, micro, zero, jnp.bfloat16)

    host_adam = {"stage": 2, "offload_optimizer": {"device": "cpu"}}
    engine, batch, vocab = build(host_adam)
    losses, _ = _train_steps(engine, batch, 3)
    _check_losses(losses, vocab)
    kinds = {x.sharding.memory_kind for x in jax.tree.leaves(
        engine.optimizer_state) if getattr(x, "ndim", 0) >= 1}
    _check(kinds == {"pinned_host"},
           f"offload_optimizer moments not in pinned_host: {kinds}")
    _say(f"offload_optimizer: losses {[round(x, 3) for x in losses]}, "
         f"moments in {sorted(kinds)}")
    engine.destroy()

    try:
        build({**host_adam, "offload_param": {"device": "cpu"}})
    except ParamOffloadUnsupportedError as e:
        _say(f"offload_param: refused by name — {type(e).__name__}")
    else:
        raise AssertionError(
            "offload_param built an engine on TPU; its train step aborts "
            "XLA (ROADMAP) and must be refused by name")

    model = _gpt(preset, n_layers, dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16, scan_layers=True,
                 max_seq_len=seq)
    params = _seeded_params(model)
    prompt = np.random.default_rng(3).integers(
        0, model.config.vocab_size, size=(1, 16), dtype=np.int32)
    resident = ds.init_inference(model, params=params, dtype=jnp.bfloat16)
    streamed = ds.init_inference(model, params=params, dtype=jnp.bfloat16,
                                 offload_params=True)
    kinds = {x.sharding.memory_kind
             for x in jax.tree.leaves(streamed.params["h"]) if x.ndim >= 3}
    _check(kinds == {"pinned_host"},
           f"ZeRO-Inference block kernels not in pinned_host: {kinds}")
    want = np.asarray(resident.generate(prompt, max_new_tokens=8))
    got = np.asarray(streamed.generate(prompt, max_new_tokens=8))
    _check((got == want).all(),
           f"host-streamed decode {got[0, -8:]} != resident {want[0, -8:]}")
    _say(f"ZeRO-Inference: block kernels in {sorted(kinds)}, 8 tokens "
         "identical to the resident engine")


# ---------------------------------------------------------------------------
# several chips
# ---------------------------------------------------------------------------

def phase_multichip(preset, seq, micro, steps, n_layers, serve):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.pallas import tuning

    n = jax.device_count()
    # ZeRO-3 partitions parameters over the mesh's ``fsdp`` axis (docs/
    # config.md); on ``{data: -1}`` they would stay replicated. fp32 master
    # + Adam moments + bf16 compute copies of 1.3B are ~21 GB: the steps
    # only fit a 16 GB chip if the state really is partitioned.
    tuning.clear_last_dispatch()
    engine, batch, vocab = _trainer(
        preset, n_layers, seq, micro, {"stage": 3}, jnp.float32,
        mesh={"data": 1, "fsdp": n})
    losses, times = _train_steps(engine, batch, steps)
    _say(f"{n}-chip train {preset} ZeRO-3: losses "
         f"{[round(x, 3) for x in losses]}; step times s "
         f"{[round(t, 3) for t in times]} (first includes compile)")
    _check_losses(losses, vocab)
    flash = tuning.last_dispatch("flash_attention")
    _check(flash, "multi-chip training never traced the flash kernel")
    for s in flash:
        _mosaic(flash[s], f"flash_attention/{s}")
    # the kernel saw ONE chip's share of the batch, not the gathered whole
    seen = tuning.last_dispatch("attention")["backend"]
    _check(seen["batch"] == micro,
           f"flash kernel ran on batch {seen['batch']}, not one chip's "
           f"micro batch {micro}: {seen}")
    # every optimizer leaf, and every param leaf above the ZeRO-3
    # persistence threshold (small params stay replicated by design),
    # spans all n devices with 1/n of its bytes on each
    persist = engine.config.zero_optimization.stage3_param_persistence_threshold
    for label, tree, floor in (("param", engine.params, persist),
                               ("optimizer", engine.optimizer_state, 0)):
        total = 0
        for leaf in jax.tree.leaves(tree):
            if getattr(leaf, "ndim", 0) < 1:
                continue
            total += leaf.nbytes
            devs = {s.device for s in leaf.addressable_shards}
            _check(len(devs) == n,
                   f"{label} leaf {leaf.shape} lives on {len(devs)} of {n} "
                   "devices")
            shard = leaf.addressable_shards[0].data
            _check(leaf.size <= floor or shard.nbytes * n == leaf.nbytes,
                   f"{label} leaf {leaf.shape} is not split {n} ways: "
                   f"{leaf.sharding}")
        _say(f"{label} state: {total / 1e9:.2f} GB, every leaf"
             + (f" above {floor} elements" if floor else "")
             + f" split {n} ways")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        _say(f"  {d}: bytes_in_use {stats.get('bytes_in_use', 0) / 1e9:.2f} "
             f"GB, peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")
    engine.destroy()
    del engine

    # tensor-parallel serving over the same chips
    smodel = _gpt(serve["preset"], serve["n_layers"], dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16, scan_layers=True,
                  max_seq_len=max(serve["max_len"], 128))
    params = _seeded_params(smodel)
    eng = ds.init_inference(smodel, params=params, dtype=jnp.bfloat16,
                            mp_size=n)
    qkv = eng.params["h"]["attn"]["qkv"]["kernel"]
    _check(len({s.device for s in qkv.addressable_shards}) == n
           and qkv.addressable_shards[0].data.nbytes * n == qkv.nbytes,
           f"mp_size={n} left the qkv kernel unsplit: {qkv.sharding}")
    reqs = _requests(np.random.default_rng(2), serve["n_requests"],
                     smodel.config.vocab_size, serve["prompt_max"],
                     serve["new_max"])
    _serve_and_check(eng, smodel, params, reqs, serve["num_slots"],
                     serve["max_len"], serve["page_len"],
                     serve["paging_kernel"], serve["logit_tol"],
                     f"{n}-chip serve {serve['preset']} mp_size={n}")
    rec = tuning.last_dispatch("paged_attention").get(
        f"page{serve['page_len']}")
    _check(rec and rec.get("model_shards") == n,
           f"paged kernel was not mapped over the model axis: {rec}")


# ---------------------------------------------------------------------------

def main():
    faulthandler.dump_traceback_later(_budget_left(), exit=True)
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _say(f"jax {jax.__version__} platform={device['platform']} "
         f"device_kind={device['kind']!r} count={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX platform is {device['platform']!r}, not "
              f"'tpu' (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}) — "
              "nothing to prove here", file=sys.stderr)
        return 2

    from deepspeed_tpu.utils.host_env import configure_compile_cache
    cache_dir = configure_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
    jax.monitoring.register_event_listener(on_event)
    _say(f"compile cache at {cache_dir}")

    phases = [("train", phase_train), ("serve", phase_serve),
              ("olmoe", phase_olmoe), ("lfm2", phase_lfm2),
              ("kanana", phase_kanana), ("falcon_h1", phase_falcon_h1),
              ("phi4flash", phase_phi4flash), ("kernels", phase_kernels)]
    if device["count"] >= 4:
        phases.append(("multichip", phase_multichip))
    else:
        _say(f"phase multichip: SKIPPED — {device['count']} device(s); it "
             "needs jax.device_count() >= 4 (chiprun --chips 4)")
    # last: a compiler abort in the host-offload pass (it has happened)
    # would take every later phase with it
    phases.append(("offload", phase_offload))
    failed = []
    for name, fn in phases:
        t0 = time.monotonic()
        h0, m0 = cache["hits"], cache["misses"]
        _say(f"phase {name}: start")
        try:
            fn(**FULL[name])
        except Exception:
            failed.append(name)
            _say(f"phase {name}: FAILED\n{traceback.format_exc()}")
        else:
            _say(f"phase {name}: PASSED in {time.monotonic() - t0:.1f}s "
                 f"(compile cache: {cache['hits'] - h0} hits, "
                 f"{cache['misses'] - m0} misses)")
    _say(f"total {time.monotonic() - _T0:.1f}s; compile cache "
         f"{cache['hits']} hits, {cache['misses']} misses")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
