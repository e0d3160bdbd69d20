"""Plain one-process measurements on the local chip(s).

Each ``bench_*`` function is one measurement at a real width (GPT-2 1.3B
ZeRO-Offload training, 125M ZeRO-1 training, 2.7B / 6.7B decode, kernel
microbenches); ROADMAP item 1.1 turns them into benchmark cells. ``main``
runs them all in THIS process — the process that holds the chip — and
prints one JSON line naming the device. There is no probe, no retry and
no partial artifact: a run that finds no TPU, or whose measurement
throws, exits nonzero at once.

1.3B on one 16 GB chip trains with the streamed host offload
(runtime/zero/offload_optimizer.py StreamedHostAdam): fp32 moments in the
TPU host's pinned memory, streamed per-leaf through HBM inside the step.
"""

import json
import os
import sys
import time

SEQ = 1024
NORTH_STAR_METRIC = "gpt2_1p3b_zero_offload_train_tokens_per_sec_per_chip"


def _interleaved_ms(jax, fns, args, reps, trials=5):
    """Time pre-warmed jitted fns: ``trials`` rounds, INTERLEAVED so any
    drift over the run hits every variant alike rather than whichever ran
    last; per-variant min; returns ms-per-rep."""
    best = {name: float("inf") for name in fns}
    for _trial in range(trials):
        for name, g in fns.items():
            t0 = time.time()
            jax.block_until_ready(g(*args))
            best[name] = min(best[name], time.time() - t0)
    return {name: t / reps * 1e3 for name, t in best.items()}


def _unrolled_timer(jax, jnp, f, args, reps):
    """REPS independent applications UNROLLED inside one jit (each on a
    perturbed first input, one scalar reduced per application): the one
    dispatch amortizes over reps without lax.scan loop overhead polluting
    ms-scale kernels. Shared by the kernel microbenches."""
    @jax.jit
    def g(*a):
        tot = jnp.float32(0)
        for i in range(reps):
            o = f(a[0] + jnp.asarray(i, a[0].dtype) * 1e-6, *a[1:])
            tot = tot + o.reshape(-1)[0].astype(jnp.float32)
        return tot
    jax.block_until_ready(g(*args))   # warm (compile)
    return g


def _train_bench(preset, config_extra, micro, gas, steps, np, jax, jnp, ds,
                 models, param_dtype=None):
    import dataclasses
    GPT, GPT2_PRESETS = models.GPT, models.GPT2_PRESETS
    gpt_chunked_loss_fn = models.gpt_chunked_loss_fn
    mcfg = dataclasses.replace(
        GPT2_PRESETS[preset], dtype=jnp.bfloat16,
        param_dtype=param_dtype or jnp.float32,
        scan_layers=True, remat="full")

    def loss_fn(model, params, batch, rng, train):
        ids = batch["input_ids"]
        # chunked vocab loss: [B,S,V] logits never materialize
        h, wte = model.apply(params, ids, deterministic=not train,
                             return_hidden=True)
        return gpt_chunked_loss_fn(h[:, :-1], wte, ids[:, 1:], chunk=128)

    n_chips = len(jax.devices())
    global_batch = micro * gas * n_chips
    config = {
        "train_batch_size": global_batch,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "steps_per_print": 10_000,
        **config_extra,
    }
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size,
                                       size=(global_batch, SEQ),
                                       dtype=np.int32)}
    engine, _, _, _ = ds.initialize(
        model=GPT(mcfg), config=config, loss_fn=loss_fn,
        sample_batch={"input_ids": batch["input_ids"][:1]},
        rng=jax.random.PRNGKey(0))
    for _ in range(2):
        loss = engine.train_batch(batch)
    jax.block_until_ready(engine.params)
    # goodput over the MEASURED window only (warmup compiles would
    # otherwise dominate the compile fraction of a 3-step bench)
    from deepspeed_tpu.observability.goodput import reset_ledger
    ledger = reset_ledger()
    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    jax.block_until_ready((loss, engine.params))
    dt = (time.time() - t0) / steps
    goodput = ledger.breakdown()
    tokens_per_sec = global_batch * SEQ / dt
    per_chip = tokens_per_sec / n_chips
    tflops = 6 * mcfg.num_params() * per_chip / 1e12
    # the HBM accountant's attribution + a live memory_stats read (real
    # hardware exposes it; null on backends without the query) — the
    # train-side ``memory`` block next to the throughput numbers
    from deepspeed_tpu.observability.memory import get_accountant
    acct = get_accountant()
    acct.sample_live()
    mem_report = acct.report()
    memory = {"by_subsystem": {tag: info["bytes"] for tag, info
                               in mem_report["by_subsystem"].items()},
              "static_total_bytes": mem_report["static_total_bytes"],
              "hbm_bytes_in_use": (mem_report["live"] or {}).get(
                  "bytes_in_use")}
    return {"tokens_per_sec_per_chip": round(per_chip, 1),
            "model_tflops_per_chip": round(tflops, 1),
            "step_ms": round(dt * 1e3, 1),
            "memory": memory,
            "goodput": {k: goodput[k] for k in
                        ("wall_s", "fractions", "goodput_fraction",
                         "badput_fraction") if k in goodput},
            "loss": round(float(loss), 3)}


def bench_zero_inference(np, jax, jnp, ds, models, preset="gpt2-6.7b",
                         tokens=3):
    """ZeRO-Inference (reference: DeepSpeedZeRoOffload standalone for
    inference, parameter_offload.py:166): serve a bf16 model whose
    weights exceed HBM by streaming the block kernels from the
    accelerator host's pinned memory per layer. 6.7B bf16 = 12.9GB of
    kernels on a 16GB chip (the int8 path quantizes; this path doesn't).
    Init lands the kernels straight in host space (out_shardings), so
    peak HBM never holds the full model."""
    import dataclasses
    import flax.core.meta as flax_meta
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.inference.generation import (init_cache, _prefill,
                                                    _decode_loop)
    dev = jax.devices()[0]
    GPT = models.GPT
    mcfg = dataclasses.replace(models.GPT2_PRESETS[preset],
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                               scan_layers=True, max_seq_len=2048)
    model = GPT(mcfg)
    ids = jnp.ones((1, 16), jnp.int32)
    shapes = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(r, ids))["params"],
        jax.random.PRNGKey(0))
    host = SingleDeviceSharding(dev, memory_kind="pinned_host")
    devs = SingleDeviceSharding(dev, memory_kind="device")
    out_sh = dict(jax.tree.map(lambda _: devs, shapes))
    out_sh["h"] = jax.tree.map(
        lambda s: host if len(s.shape) >= 3 else devs, shapes["h"])
    params = jax.jit(
        lambda r: flax_meta.unbox(model.init(r, ids))["params"],
        out_shardings=out_sh)(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    hb = sum(x.nbytes for x in jax.tree.leaves(params["h"])
             if x.sharding.memory_kind == "pinned_host")
    eng = ds.init_inference(GPT(mcfg), params=params, dtype=jnp.bfloat16,
                            offload_params=True, max_tokens=128)
    cache = init_cache(eng.module, eng.params, 1, 128)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, mcfg.vocab_size, size=(1, 32)),
                         jnp.int32)
    logits, cache = _prefill(eng.module, eng.params, cache, prompt,
                             jnp.arange(32), None)
    last = jnp.argmax(logits[:, -1, :], axis=-1)
    lat = []
    for i in range(tokens + 1):          # +1 warm-up (compile)
        t0 = time.time()
        toks, cache = _decode_loop(eng.module, eng.params, cache, last,
                                   jnp.int32(32 + i), 1, 0.0, None, None,
                                   jax.random.PRNGKey(1), None)
        last = toks[:, -1]
        _ = np.asarray(last)
        lat.append(time.time() - t0)
    warm = sorted(lat[1:])[len(lat[1:]) // 2]
    return {"model": preset + "-bf16-offload",
            "host_streamed_gb": round(hb / 1e9, 1),
            "s_per_token": round(warm, 2),
            "effective_host_bw_gbps": round(hb / 1e9 / warm, 1),
            "note": "weights exceed HBM; kernels stream from pinned host "
                    "memory per layer (ZeRO-Inference)"}


def bench_1p3b(np, jax, jnp, ds, models):
    """North star: GPT-2 1.3B, ZeRO-2 + streamed host Adam offload.

    micro=8 fills HBM (micro=16 OOMs at 1.3B/full-remat; lighter remat
    policies — dots/dots_no_batch — fail to compile at micro=8, measured
    2026-07-31). gas=64 puts the global batch at 512 seqs (524k tokens —
    GPT-3 trained its 1.3B config at 1M-token batches, so ordinary) and
    amortizes the once-per-step host moment streaming near its
    asymptote. Measured sweep on v5e (2026-07-30 .. 31): micro4/gas8
    61.5, micro8/gas4 67.1, micro8/gas8 80.1, micro8/gas16 89.6,
    micro8/gas32 95.1, micro8/gas64 97.8 TFLOPS; micro4/gas32/dots 87.5
    (recompute savings don't beat the fatter micro); micro8/gas128
    crashes the TPU worker (2026-07-31) — do not raise further."""
    return _train_bench(
        "gpt2-1.3b",
        {"zero_optimization": {"stage": 2,
                               "offload_optimizer": {"device": "cpu"}}},
        micro=8, gas=64, steps=3, np=np, jax=jax, jnp=jnp, ds=ds,
        models=models, param_dtype=jnp.bfloat16)


def bench_125m(np, jax, jnp, ds, models):
    """BASELINE config #1 (sans cpu_adam: see module docstring)."""
    return _train_bench(
        "gpt2-125m", {"zero_optimization": {"stage": 1}},
        micro=32, gas=1, steps=5, np=np, jax=jax, jnp=jnp, ds=ds,
        models=models)


def bench_decode(np, jax, jnp, models, preset="gpt2-2.7b", prompt=128,
                 tokens=64, int8=False, throughput_batch=None):
    """Serving p50: largest GPT-class config fitting one chip in bf16,
    Pallas decode-attention kernel, preallocated KV cache. ``int8=True``
    stores weights int8 (per-channel scales) — the weight-only quantized
    serving path (reference: *_int8 gemms). ``throughput_batch``
    additionally measures the batched decode loop (weights stream once
    per step for the whole batch — the serving-throughput side of the
    latency/throughput trade)."""
    import dataclasses
    from deepspeed_tpu.inference.generation import (init_cache, _prefill,
                                                    _decode_loop)
    GPT, GPT2_PRESETS = models.GPT, models.GPT2_PRESETS
    mcfg = dataclasses.replace(GPT2_PRESETS[preset], dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16, scan_layers=True,
                               max_seq_len=2048)
    model = GPT(mcfg)
    ids = jnp.ones((1, 16), jnp.int32)
    import flax.core.meta as flax_meta
    transform = None
    if int8:
        # direct consumption: kernels stay int8 dicts, QDense runs the
        # fused-dequant matmul — no per-step dequantized bf16 copy.
        # Quantize INSIDE the init jit: each bf16 leaf dies right after
        # its quantize, so peak HBM ~ int8 model + largest bf16 leaf —
        # how 6.7B (13.4GB bf16) initializes on a 16GB chip at all.
        from deepspeed_tpu.module_inject.module_quantize import \
            quantize_param_tree
        params = jax.jit(lambda r: quantize_param_tree(
            flax_meta.unbox(model.init(r, ids))["params"],
            only_kernels=True))(jax.random.PRNGKey(0))
    else:
        params = jax.jit(
            lambda r: flax_meta.unbox(model.init(r, ids))["params"])(
                jax.random.PRNGKey(0))

    cache_len = 1024
    cache = init_cache(model, params, 1, cache_len)
    rng = np.random.default_rng(0)
    prompt_ids = jnp.asarray(rng.integers(0, mcfg.vocab_size,
                                          size=(1, prompt)), jnp.int32)
    logits, cache = _prefill(model, params, cache, prompt_ids,
                             jnp.arange(prompt), transform)
    last = jnp.argmax(logits[:, -1, :], axis=-1)

    # single-token decode latency (the DS-Inference p50 metric): one
    # jitted step per token, timed per call
    def one(cache, last, pos):
        toks, cache = _decode_loop(model, params, cache, last,
                                   pos, 1, 0.0, None, None,
                                   jax.random.PRNGKey(1), transform)
        return toks[:, -1], cache
    pos = jnp.int32(prompt)
    last_t, cache = one(cache, last, pos)   # compile
    _ = np.asarray(last_t)
    lat = []
    for i in range(tokens):
        t0 = time.time()
        last_t, cache = one(cache, last_t, pos + 1 + i)
        _ = np.asarray(last_t)
        lat.append((time.time() - t0) * 1e3)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p90 = lat[int(len(lat) * 0.9)]

    # amortized: one scan over 64 tokens on-device (no per-token dispatch).
    # num_steps is a jit-static arg: warm the 64-step executable first so
    # the timed window excludes its compile.
    _toks, cache = _decode_loop(model, params, cache, last_t,
                                pos + tokens + 1, 64, 0.0, None, None,
                                jax.random.PRNGKey(2), transform)
    _ = np.asarray(_toks[0, -1])
    t0 = time.time()
    toks, cache = _decode_loop(model, params, cache, last_t,
                               pos + tokens + 1, 64, 0.0, None, None,
                               jax.random.PRNGKey(2), transform)
    _ = np.asarray(toks[0, -1])
    amort = (time.time() - t0) * 1e3 / 64

    result = {"model": preset + ("-int8" if int8 else ""),
              "p50_ms_per_token": round(p50, 2),
              "p90_ms_per_token": round(p90, 2),
              "amortized_ms_per_token": round(amort, 2),
              "tokens_per_sec_batch1": round(1e3 / amort, 1),
              "note": "p50/p90_ms_per_token = one host dispatch per token; "
                      "amortized = 64-token on-device loop"}
    if throughput_batch:
        del cache   # free batch-1 cache before the batched one lands
        b = throughput_batch
        bcache = init_cache(model, params, b, cache_len)
        bprompt = jnp.asarray(rng.integers(0, mcfg.vocab_size,
                                           size=(b, prompt)), jnp.int32)
        blogits, bcache = _prefill(model, params, bcache, bprompt,
                                   jnp.arange(prompt), transform)
        blast = jnp.argmax(blogits[:, -1, :], axis=-1)
        bt, bcache = _decode_loop(model, params, bcache, blast,
                                  jnp.int32(prompt), 64, 0.0, None,
                                  None, jax.random.PRNGKey(3), transform)
        _ = np.asarray(bt[0, -1])   # warm the batched 64-step exec
        t0 = time.time()
        bt, bcache = _decode_loop(model, params, bcache, bt[:, -1],
                                  jnp.int32(prompt + 64), 64, 0.0,
                                  None, None, jax.random.PRNGKey(4),
                                  transform)
        _ = np.asarray(bt[0, -1])
        bdt = time.time() - t0
        result[f"tokens_per_sec_batch{b}"] = round(b * 64 / bdt, 1)
        result[f"amortized_ms_per_token_batch{b}"] = round(
            bdt * 1e3 / 64, 2)
    return result


def bench_sparse_kernel(np, jax, jnp, seq=8192, heads=8, d=64, batch=2):
    """Block-sparse Pallas kernel vs the dense flash path at seq 8k.
    Longformer-style sliding-window + global pattern: the long-context
    workhorse layout (density 0.077 at 8k).

    Timing: ONE kernel launch covering ``batch`` samples (the grid's
    leading dim), REPS independent applications unrolled inside one jit,
    min of 5 interleaved trials."""
    from deepspeed_tpu.ops.sparse_attention import (BSLongformerSparsityConfig,
                                                    sparse_attention)
    from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import \
        compile_layout
    from deepspeed_tpu.ops.transformer.attention import attention
    cfg = BSLongformerSparsityConfig(num_heads=heads, block=16,
                                     num_sliding_window_blocks=8,
                                     global_block_indices=[0])
    plan = compile_layout(cfg, seq)
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.standard_normal((batch, seq, heads, d)),
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    REPS = 32
    make = lambda f: _unrolled_timer(jax, jnp, f, (q, k, v), REPS)

    # both paths are opaque pallas_calls (no DCE asymmetry)
    fns = {"sparse": make(lambda a, b, c: sparse_attention(
               a, b, c, cfg, backend="pallas")),
           "dense": make(lambda a, b, c: attention(
               a, b, c, causal=False, seq_parallel="none"))}
    ms = _interleaved_ms(jax, fns, (q, k, v), REPS)
    return {"seq": seq, "layout_density": round(plan.density, 3),
            "sparse_ms": round(ms["sparse"], 2),
            "dense_ms": round(ms["dense"], 2),
            "speedup": round(ms["dense"] / ms["sparse"], 2)}


def bench_flash_dropout(np, jax, jnp, batch=2, seq=2048, heads=16, d=64,
                        reps=8):
    """Fused attention dropout: flash kernel with in-kernel counter-based
    keep sampling vs the dense O(s^2) softmax+dropout chain, and the fused
    kernel's dropout overhead vs plain flash — the VPU hash rides under
    the MXU matmuls."""
    from deepspeed_tpu.ops.pallas import flash_attention
    from deepspeed_tpu.ops.transformer.attention import _reference_attention
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.standard_normal((batch, seq, heads, d)), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    key = jax.random.PRNGKey(3)
    make = lambda f: _unrolled_timer(jax, jnp, f, (q, k, v), reps)

    fns = {"flash_dropout": make(lambda a, b, c: flash_attention(
               a, b, c, causal=True, dropout_rate=0.1, dropout_rng=key)),
           "flash_plain": make(lambda a, b, c: flash_attention(
               a, b, c, causal=True)),
           "dense_dropout": make(lambda a, b, c: _reference_attention(
               a, b, c, causal=True, dropout_rate=0.1, dropout_rng=key,
               deterministic=False))}
    ms = _interleaved_ms(jax, fns, (q, k, v), reps)
    fd, fp, dd = (ms[k] for k in ("flash_dropout", "flash_plain",
                                  "dense_dropout"))
    return {"seq": seq,
            "flash_dropout_ms": round(fd, 3),
            "flash_plain_ms": round(fp, 3),
            "dense_dropout_ms": round(dd, 3),
            "speedup_vs_dense": round(dd / fd, 2),
            "dropout_overhead_pct": round((fd / fp - 1) * 100, 1)}


def bench_fused_epilogue(np, jax, jnp, d=4096, reps=400):
    """Substantiates the design claim that XLA fuses the bias+GELU
    epilogue into the matmul (why there is no hand-written gelu kernel;
    reference hand-fuses it in csrc/transformer/gelu_kernels.cu): the
    fused chain must cost ~the bare matmul.

    The carried reduction must consume the FULL output — reducing o[0,0]
    lets XLA shrink some variants but not others, which reads as a fake
    "epilogue overhead"."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((d, d)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((d, d)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((d,)), jnp.bfloat16)

    def make(fn):
        @jax.jit
        def g(x, w, b):
            def body(c, _):
                o = fn(x + c, w, b)
                # full-output reduction: nothing is DCE-able
                s = jnp.sum(o.astype(jnp.float32)).astype(jnp.bfloat16)
                return c + s * jnp.bfloat16(1e-12), None
            c, _ = jax.lax.scan(body, jnp.bfloat16(0.), None, length=reps)
            return c
        jax.block_until_ready(g(x, w, b))   # warm (compile)
        return g

    fns = {"mm": make(lambda x, w, b: jnp.dot(x, w)),
           "full": make(lambda x, w, b: jax.nn.gelu(jnp.dot(x, w) + b))}
    ms = _interleaved_ms(jax, fns, (x, w, b), reps)
    t_mm, t_full = ms["mm"], ms["full"]
    return {"matmul_ms": round(t_mm, 3),
            "matmul_bias_gelu_ms": round(t_full, 3),
            "matmul_tflops": round(2 * d ** 3 / (t_mm * 1e-3) / 1e12, 1),
            "epilogue_overhead_pct": round((t_full / t_mm - 1) * 100, 1)}


def bench_offload(np, jax, jnp, ds, models, steps=10, warmup=2,
                  d_model=192, n_layers=4, seq=128, batch_rows=16):
    """Tiered-residency offload scenario (runtime/tiering/,
    docs/offload.md) on the CPU backend: the same model + batches train
    under {all_resident, host_offload, host_disk} plans against a
    SYNTHETIC device budget smaller than params+optimizer state, plus a
    prefetch-off control arm at the host_disk plan.

    What the artifact proves (and how):
    - steps/s per plan — the residency cost in wall clock;
    - the goodput ledger's ``data_stall`` fraction per arm (PR 8's
      instrument, reset after warmup so the window is clean): prefetch
      ON vs OFF at the SAME plan must show the stall fraction dropping —
      overlap measured, not claimed;
    - bitwise parity: every plan's final params equal the all_resident
      arm's (the tiering acceptance invariant);
    - per-tier residency (``mem/by_tier/*``) and transfer-byte deltas
      from the metrics registry.
    """
    import tempfile
    from deepspeed_tpu.models import GPT, GPTConfig, gpt_loss_fn
    from deepspeed_tpu.observability.goodput import get_ledger, reset_ledger
    from deepspeed_tpu.observability.metrics import get_registry

    vocab = 512
    mc = GPTConfig(vocab_size=vocab, max_seq_len=seq, d_model=d_model,
                   n_layers=n_layers, n_heads=d_model // 32,
                   dtype=jnp.float32, scan_layers=True)

    def loss_fn(model, params, batch, rng, train):
        ids = batch["input_ids"]
        logits = model.apply(params, ids, deterministic=not train)
        return gpt_loss_fn(logits[:, :-1], ids[:, 1:])

    def make_batch(seed):
        r = np.random.default_rng(seed)
        return {"input_ids": r.integers(0, vocab, size=(batch_rows, seq),
                                        dtype="int32")}

    # size the synthetic hierarchy so the model does NOT fit the device
    # budget and the host budget forces a real disk spill
    n_params = 12 * d_model * d_model * n_layers + vocab * d_model * 2 \
        + seq * d_model
    state_bytes = n_params * 4 * 3          # params + two fp32 moments
    hbm_budget = state_bytes // 3           # < params + moments
    host_budget = state_bytes // 3

    work = tempfile.mkdtemp(prefix="ds_tpu_bench_offload_")
    arms = {
        "all_resident": {"plan": "all_resident"},
        "host_offload": {"plan": "host_offload"},
        "host_disk": {"plan": "host_disk",
                      "host_budget_bytes": host_budget},
        "host_disk_noprefetch": {"plan": "host_disk",
                                 "host_budget_bytes": host_budget,
                                 "prefetch": False},
    }
    results, params_by_arm = {}, {}
    for arm, knobs in arms.items():
        tiering = {"enabled": True, "probe_bandwidth": arm == "all_resident",
                   "hbm_budget_bytes": hbm_budget,
                   "disk_path": os.path.join(work, arm), **knobs}
        cfg = {"train_batch_size": batch_rows,
               "train_micro_batch_size_per_gpu":
                   batch_rows // jax.device_count(),
               "gradient_accumulation_steps": 1,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 10 ** 9, "tiering": tiering}
        engine, _, _, _ = ds.initialize(
            model=GPT(mc), config=cfg, loss_fn=loss_fn,
            sample_batch=make_batch(0), rng=jax.random.PRNGKey(0))
        for s in range(warmup):
            engine.train_batch(make_batch(s))
        reg = get_registry()

        def xfer():
            snap = reg.snapshot().get("counters") or {}
            return {k: v for k, v in snap.items()
                    if k.startswith("tiering/transfer_bytes/")}
        before = xfer()
        reset_ledger()
        t0 = time.time()
        for s in range(warmup, warmup + steps):
            engine.train_batch(make_batch(s))
        wall = time.time() - t0
        breakdown = get_ledger().breakdown()
        after = xfer()
        if engine.tiering is not None:
            engine.params, engine.optimizer_state = engine.tiering.stage_in(
                engine.params, engine.optimizer_state)
        params_by_arm[arm] = [np.array(x)
                              for x in jax.tree.leaves(engine.params)]
        gauges = reg.snapshot().get("gauges") or {}
        results[arm] = {
            "steps_per_sec": round(steps / wall, 3),
            "wall_s": round(wall, 3),
            "goodput": {
                "fractions": {k: round(v, 5)
                              for k, v in breakdown["fractions"].items()},
                "seconds": {k: round(v, 5)
                            for k, v in breakdown["seconds"].items()},
            },
            "data_stall_fraction": round(
                breakdown["fractions"]["data_stall"], 5),
            "mem_by_tier": {k.split("/")[-1]: int(v)
                            for k, v in gauges.items()
                            if k.startswith("mem/by_tier/")},
            "transfer_bytes": {k.split("/")[-1]:
                               int(after.get(k, 0) - before.get(k, 0))
                               for k in after},
            "plan": engine.tiering.report()["plan"]["name"],
        }
        engine.destroy()
    ref = params_by_arm["all_resident"]
    for arm, leaves in params_by_arm.items():
        results[arm]["bitwise_match_all_resident"] = bool(
            all(np.array_equal(a, b) for a, b in zip(ref, leaves)))
    stall_on = results["host_disk"]["data_stall_fraction"]
    stall_off = results["host_disk_noprefetch"]["data_stall_fraction"]
    return {
        "model": {"params": int(n_params), "d_model": d_model,
                  "n_layers": n_layers, "seq": seq,
                  "state_bytes": int(state_bytes)},
        "budgets": {"hbm_budget_bytes": int(hbm_budget),
                    "host_budget_bytes": int(host_budget)},
        "arms": results,
        "prefetch_stall_fraction_on": stall_on,
        "prefetch_stall_fraction_off": stall_off,
        "prefetch_overlap_proven": bool(stall_on < stall_off),
    }


def offload_main(argv):
    """``python bench.py --offload [--out PATH] [--steps N]``: the
    tiering scenario. Always the CPU backend — this bench's whole point
    is to run where the HBM budget is synthetic; its seconds are not
    device numbers."""
    out_path = "BENCH_offload.json"
    steps = 10
    if "--out" in argv:
        out_path = argv[argv.index("--out") + 1]
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    import deepspeed_tpu.models as models
    extra = {"offload": bench_offload(np, jax, jnp, ds, models, steps=steps)}
    artifact = {
        "metric": "offload_data_stall_fraction_prefetch_on",
        "value": extra["offload"]["prefetch_stall_fraction_on"],
        "unit": "fraction of wall clock (goodput ledger)",
        "device": _device(jax),
        "extra": extra,
    }
    line = json.dumps(artifact)
    print(line)
    with open(out_path, "w") as f:
        f.write(line + "\n")


def _device(jax):
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main():
    import numpy as np
    import jax
    device = _device(jax)
    print(f"# jax {jax.__version__} {device}", file=sys.stderr, flush=True)
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: JAX platform is "
            f"{device['platform']!r}, not 'tpu' (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); nothing was measured")
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    import deepspeed_tpu.models as models

    extra = {}

    def run(name, fn, *a, **kw):
        # a measurement that throws ends the run with its traceback and a
        # nonzero exit code; nothing is caught into the result
        extra[name] = fn(*a, **kw)
        print(f"# {name}: {extra[name]}", file=sys.stderr, flush=True)

    # kernel microbenches first, then decode: both want a quiet chip.
    # Measured 2026-07-31: running the sparse microbench AFTER the
    # training benches read 10.8ms sparse / 8.5ms dense (0.78x) vs
    # 5.2ms / 12.4ms (2.4x) on a fresh backend — training-engine
    # allocator residue distorts kernel-scale timings, so order matters.
    run("sparse_attention_8k", bench_sparse_kernel, np, jax, jnp)
    run("flash_dropout_2k", bench_flash_dropout, np, jax, jnp)
    run("fused_epilogue", bench_fused_epilogue, np, jax, jnp)
    run("decode", bench_decode, np, jax, jnp, models)
    run("decode_int8", bench_decode, np, jax, jnp, models, int8=True)
    # the capability headline: 6.7B (GPT-3-class, the BLOOM-7B-class
    # BASELINE #5 analog) on ONE 16GB chip — only possible int8 (13.4GB
    # bf16 weights + cache exceed HBM; 6.7GB int8 + bf16 embeddings fit)
    run("decode_int8_6p7b", bench_decode, np, jax, jnp, models,
        preset="gpt2-6.7b", int8=True, throughput_batch=8)
    # same 6.7B servable WITHOUT quantization: bf16 weights exceed HBM
    # and stream from pinned host memory (ZeRO-Inference)
    run("decode_6p7b_bf16_zero_inference", bench_zero_inference,
        np, jax, jnp, ds, models)
    run("gpt2_1p3b_zero_offload", bench_1p3b, np, jax, jnp, ds, models)
    run("gpt2_125m_zero1", bench_125m, np, jax, jnp, ds, models)

    print(json.dumps({
        "metric": NORTH_STAR_METRIC,
        "value": extra["gpt2_1p3b_zero_offload"]["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip",
        "device": device,
        "extra": extra,
    }))


if __name__ == "__main__":
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    configure_compile_cache()
    if "--offload" in sys.argv[1:]:
        offload_main(sys.argv[1:])
    else:
        main()
