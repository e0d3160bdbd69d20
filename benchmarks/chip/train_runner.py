"""A training cell: ``ds.initialize`` -> ``engine.train_batch``, a fresh
seeded batch made on the host for every step, for ``--seconds``."""

import math
import time

import numpy as np

from . import families, model as bench_model
from . import reference, traffic as traffic_mod, tracing
from .phases import peak_bytes
from .stats import rate

# The engine's loss against the plain float32 reference's on the same
# parameters, over CHECK_GROUPS groups of seeded rows: the number compared
# is the root mean square of the groups' differences. One difference
# alone has either sign and reads near zero by chance whatever the
# arithmetic (the mean of thousands of positions, each moved by the
# logits' rounding): under the single-group limit of 4e-3 that stood
# until PR 27 the control was never refused. The rms over sixteen grows
# in step with the logits' error and is steady from seed to seed. On the
# chip (PERF.md section 2): sound runs' largest 9.3e-5 over 29 readings
# of both training cells; the control's smallest — the reference with
# fp8 weights in the engine's place — 2.3e-4 over 16.
LOSS_RMS_TOL = 1.5e-4
CHECK_GROUPS = 16


def _engine_config(config, micro, chips):
    out = dict(config["engine"])
    out.update({"train_batch_size": micro * chips,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1_000_000})
    return out


# the steps of a rehearsal's window, whatever --seconds says (below)
REHEARSAL_STEPS = 8


def run(cell, args, phases, compile_log, devices, say):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.pallas import tuning

    config = cell.config
    family = families.load(config)
    mix = traffic_mod.resolve(cell.traffic, args.rehearse)
    sizes = family.sizes(config, args.rehearse)
    chips = len(devices)
    seq = mix["seq"]
    micro = mix.get("micro_per_chip", config.get("micro_per_chip"))
    rows = micro * chips
    vocab = sizes["vocab_size"]
    tokens_per_step = rows * seq

    tuning.clear_last_dispatch()
    module = family.build(config, args.rehearse)
    engine, _, _, _ = ds.initialize(
        model=module, loss_fn=family.train_loss(config),
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
        families.check_kernels(tuning.last_dispatch,
                               family.expected_kernels(None))
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
    if args.rehearse:
        # a rehearsal proves control flow, never a speed: a fixed number
        # of steps, so that what `correct` compares - the loss on the
        # parameters the window leaves - is the seed's and not the
        # host's load's (PERF.md section 7: at 64 wide the rms wanders
        # 0.9e-4 to 1.6e-4 with the count of steps against 1.5e-4: it
        # read 1.63e-4 when 54 steps fitted 1.5 s)
        for _ in range(REHEARSAL_STEPS):
            now = step()
    else:
        while now - window_start < args.seconds or not step_s:
            now = step()
    window_s = now - measured_from
    compiled = compile_log.since(in_window)
    peak = peak_bytes(devices)      # before the reference check's own

    losses = [float(x) for x in losses]
    finite = all(math.isfinite(x) for x in losses)
    t0 = time.monotonic()
    check = _reference_check(family, engine, config, sizes, seq, chips,
                             args.seed)
    say(f"reference check: rms of (engine loss - float32 reference loss) "
        f"over {CHECK_GROUPS} groups of {max(2, chips)} rows "
        f"{check['rms']:.3e} (tolerance {LOSS_RMS_TOL:.1e}), largest "
        f"{check['max']:.2e}, mean loss {check['engine']:.6f} vs "
        f"{check['reference']:.6f}, in {time.monotonic() - t0:.1f}s; "
        f"window losses {losses[0]:.4f} .. {losses[-1]:.4f}, "
        f"{'all finite' if finite else 'NOT ALL FINITE'}")
    if compiled["compile_events"]:
        say(f"COMPILED INSIDE THE WINDOW: {compiled['compiled']}")
    correct = (finite and check["rms"] <= LOSS_RMS_TOL
               and compiled["compile_events"] == 0)
    engine.destroy()

    tokens_per_s_chip = rate(len(step_s) * tokens_per_step, window_s) / chips
    say(f"samples: {len(step_s)} steps of {tokens_per_step} tokens in "
        f"{window_s:.3f}s")
    return {
        "correct": bool(correct), "attempted": len(step_s),
        "failed": sum(not math.isfinite(x) for x in losses),
        "window_start": window_start, "memory_peak_bytes": peak,
        "check": {
            "loss_rms": {"value": check["rms"], "limit": LOSS_RMS_TOL},
            "losses_not_finite": {
                "value": sum(not math.isfinite(x) for x in losses),
                "limit": 0},
            "compiles_in_window": {"value": compiled["compile_events"],
                                   "limit": 0}},
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip},
        "observed": {
            "series": {"train_step_ms": [1e3 * s for s in step_s]},
            "tokens_per_s_chip": tokens_per_s_chip,
            "ops_per_token": family.ops_per_token(sizes, seq),
            "sizes": sizes, "seq": seq, "micro": micro, "chips": chips,
            "compiles_in_window": compiled["compile_events"],
            "compile_mark_at_window": in_window,
        },
        "capture": capture,
    }


def check_rows(sizes, seq, chips, seed):
    """``[CHECK_GROUPS, rows, seq + 1]`` seeded tokens: the check's own
    rows, none of the window's."""
    return np.random.default_rng(seed + 1).integers(
        0, sizes["vocab_size"],
        size=(CHECK_GROUPS, max(2, chips), seq + 1), dtype=np.int32)


def reference_losses(family, params, groups, sizes, config):
    """The plain float32 reference's mean loss on each group of rows."""
    import jax
    import jax.numpy as jnp
    with reference.highest():
        loss = jax.jit(
            lambda p, x: jnp.mean(family.reference_next_token_losses(
                p, x, sizes, config)))
        return np.array([float(loss(params, jnp.asarray(g)))
                         for g in groups])


def rms(diffs):
    return float(np.sqrt(np.mean(np.square(diffs))))


def engine_params(engine):
    import flax.core.meta as flax_meta
    params = flax_meta.unbox(engine.params)
    return params.get("params", params)


def _reference_check(family, engine, config, sizes, seq, chips, seed):
    """The engine's loss on each group of seeded rows against the plain
    float32 reference on the same parameters. Runs after the window, so
    neither its compile nor its run is in ``setup_s``."""
    groups = check_rows(sizes, seq, chips, seed)
    got = np.array([float(engine.eval_batch({"input_ids": g}))
                    for g in groups])
    want = reference_losses(family, engine_params(engine), groups, sizes,
                            config)
    return {"engine": float(got.mean()), "reference": float(want.mean()),
            "rms": rms(got - want), "max": float(np.abs(got - want).max())}
