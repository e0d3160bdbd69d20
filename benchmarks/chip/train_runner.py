"""A training cell: ``ds.initialize`` -> ``engine.train_batch``, a fresh
seeded batch made on the host for every step, for ``--seconds``."""

import math
import time

import numpy as np

from . import model as bench_model
from . import reference, traffic as traffic_mod, tracing
from .phases import peak_bytes
from .stats import rate

# |engine loss - float32 reference loss| on the check rows. The loss of a
# random-weight model sits within a few tenths of ln(vocab) and each row
# averages over `seq` positions, so the two agree closely: bf16 rounding
# of the logits (std ~0.5) moves single positions by ~1e-2 with either
# sign and the mean of thousands of positions by ~1e-3 (measured on the
# chip: see PERF.md). A forward that attends to the wrong tokens
# decorrelates each position's logit from its label and moves the mean by
# sigma_token / sqrt(positions) ~ 0.55 / sqrt(2048) = 1.2e-2.
LOSS_TOL = 4e-3


def _engine_config(config, micro, chips):
    out = dict(config["engine"])
    out.update({"train_batch_size": micro * chips,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1_000_000})
    return out


def run(cell, args, phases, compile_log, devices, say):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.pallas import tuning

    config = cell.config
    mix = traffic_mod.resolve(cell.traffic, args.rehearse)
    sizes = bench_model.sizes(config, args.rehearse)
    chips = len(devices)
    seq = mix["seq"]
    micro = mix.get("micro_per_chip", config.get("micro_per_chip"))
    rows = micro * chips
    vocab = sizes["vocab_size"]
    tokens_per_step = rows * seq

    tuning.clear_last_dispatch()
    module = bench_model.build_gpt(config, args.rehearse,
                                   remat=config["remat"])
    engine, _, _, _ = ds.initialize(
        model=module, loss_fn=bench_model.chunked_loss(config["loss_chunk"]),
        rng=bench_model.prng_key(args.seed),
        sample_batch={"input_ids": np.zeros((1, seq), np.int32)},
        config=_engine_config(config, micro, chips))
    phases.mark("build")

    rng = np.random.default_rng(args.seed)
    losses, step_s = [], []
    ready = [traffic_mod.train_batch(rng, rows, seq, vocab)]

    def step(record=True):
        t0 = time.monotonic()
        with tracing.annotate("bench/train_batch"):
            loss = engine.train_batch(ready.pop())
            # the input pipeline makes the next batch while the device
            # runs this one, as a prefetching loader does
            ready.append(traffic_mod.train_batch(rng, rows, seq, vocab))
            jax.block_until_ready((loss, engine.params))
        now = time.monotonic()
        if record:
            losses.append(loss)
            step_s.append(now - t0)
        return now

    step(record=False)
    phases.mark("first_call")
    step(record=False)                  # a fixed number of warm calls: 2
    window_start = phases.mark("warmup")
    if not args.rehearse:
        _assert_flash(tuning)
    in_window = compile_log.mark()

    capture = None
    if args.trace:
        with tracing.capture(cell, args) as capture:
            for _ in range(config.get("trace_steps", 3)):
                step(record=False)
        measured_from = time.monotonic()
    else:
        measured_from = window_start
    now = measured_from
    while now - window_start < args.seconds or not step_s:
        now = step()
    window_s = now - measured_from
    compiled = compile_log.since(in_window)
    peak = peak_bytes(devices)      # before the reference check's own

    losses = [float(x) for x in losses]
    finite = all(math.isfinite(x) for x in losses)
    t0 = time.monotonic()
    check = _reference_check(engine, module, config, sizes, seq, chips,
                             args.seed)
    say(f"reference check: engine loss {check['engine']:.6f} vs float32 "
        f"reference {check['reference']:.6f}, |diff| {check['diff']:.2e} "
        f"(tolerance {LOSS_TOL:.0e}) in {time.monotonic() - t0:.1f}s; "
        f"window losses {losses[0]:.4f} .. {losses[-1]:.4f}, "
        f"{'all finite' if finite else 'NOT ALL FINITE'}")
    if compiled["compile_events"]:
        say(f"COMPILED INSIDE THE WINDOW: {compiled['compiled']}")
    correct = (finite and check["diff"] <= LOSS_TOL
               and compiled["compile_events"] == 0)
    engine.destroy()

    tokens_per_s_chip = rate(len(step_s) * tokens_per_step, window_s) / chips
    say(f"samples: {len(step_s)} steps of {tokens_per_step} tokens in "
        f"{window_s:.3f}s")
    return {
        "correct": bool(correct), "attempted": len(step_s),
        "failed": sum(not math.isfinite(x) for x in losses),
        "window_start": window_start, "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip},
        "observed": {
            "series": {"train_step_ms": [1e3 * s for s in step_s]},
            "tokens_per_s_chip": tokens_per_s_chip,
            "sizes": sizes, "seq": seq, "micro": micro, "chips": chips,
            "compiles_in_window": compiled["compile_events"],
            "compile_mark_at_window": in_window,
        },
        "capture": capture,
    }


def _assert_flash(tuning):
    """The step that was warmed ran the Mosaic flash kernel, forward and
    backward — not the interpreter and not the jnp path."""
    choice = tuning.last_dispatch("attention").get("backend")
    flash = tuning.last_dispatch("flash_attention")
    if not (choice and choice["backend"] == "pallas"):
        raise RuntimeError(f"attention did not dispatch flash: {choice}")
    for name, rec in flash.items():
        if rec.get("interpret") is not False:
            raise RuntimeError(f"flash_attention/{name} interpreted: {rec}")
    if not (any(s.startswith("fwd_") for s in flash)
            and any(s.startswith("bwd_") for s in flash)):
        raise RuntimeError(f"flash fwd+bwd not both traced: {list(flash)}")


def _reference_check(engine, module, config, sizes, seq, chips, seed):
    """The engine's loss on a few seeded rows against the plain float32
    reference on the same parameters. Runs after the window, so neither
    its compile nor its run is in ``setup_s``."""
    import jax
    import jax.numpy as jnp
    import flax.core.meta as flax_meta
    rows = max(2, chips)
    ids = np.random.default_rng(seed + 1).integers(
        0, sizes["vocab_size"], size=(rows, seq + 1), dtype=np.int32)
    got = float(engine.eval_batch({"input_ids": ids}))
    params = flax_meta.unbox(engine.params)
    params = params.get("params", params)
    with reference.highest():
        want = float(jax.jit(
            lambda p, x: jnp.mean(reference.next_token_losses(
                p, x, sizes["n_head"], config["layer_norm_epsilon"])))(
                    params, jnp.asarray(ids)))
    return {"engine": got, "reference": want, "diff": abs(got - want)}
