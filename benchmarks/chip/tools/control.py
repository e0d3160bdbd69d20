"""The control of a cell's ``correct``, and the sound runs' readings
beside it: the two numbers every limit is set from (PERF.md section 2).
One process, no timed window, the cell's own size, one set of weights a
seed. The control has to come out as not correct.

A serving cell: the program with its own path of the next precision down
switched on — int8 weight-only serving (``init_inference(...,
quantize_weights=True)``) where the configuration states bf16 — judged by
the run's own comparison (``serve_runner._reference_check``), on the
first ``--requests`` of the cell's mix, served as configured on every
seed and from int8 weights on the first ``--control-seeds``.

A training cell: the program has no lower path, so the control is the
plain reference put in the program's place with its weights rounded to
int8 and to fp8 (``reference.lower_weights``), on the engine's own
parameters after ``--steps`` steps, judged by the run's own comparison
(``train_runner``: the rms over the check's groups of rows).

    chiprun -- python3 benchmarks/chip/tools/control.py \
        --workload serve-1p3b-chat --seeds 12 --control-seeds 6 [--rehearse]

Every token's gap and every group's loss goes to
``chiprun_out/control/<workload>-<first>.json``."""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

BASE_SEED = 2147562389      # past 2**31, and none of sets.py's


def serve_seed(ctx, seed, with_control):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu as ds
    from benchmarks.chip import model, serve_runner, traffic
    cell, args, family, config = ctx
    mix = traffic.resolve(cell.traffic, args.rehearse)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    module = family.build(config, args.rehearse)
    params = model.seeded_params(module, seed)
    row, readings = {"seed": seed}, {}
    arms = [("as_configured", False)] + [("int8", True)] * with_control
    for name, quantize in arms:
        if quantize:
            # the same values in float32, in the served weights' place
            # (both do not fit beside the pool): the program's quantizer
            # passes a bfloat16 leaf through untouched (numpy does not
            # count bfloat16 among its floating types), and the control
            # would be the sound run again
            params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        srv = ds.init_inference(
            module, params=params, quantize_weights=quantize,
            dtype=getattr(jnp, config["compute_dtype"])
        ).serve(dict(serving))
        stream = traffic.RequestStream(mix, seed, sizes["vocab_size"])
        records = [serve_runner.Record(stream.take(), 0.0)
                   for _ in range(args.requests)]
        for rec in records:
            serve_runner.submit(srv, rec)
        srv.run()
        srv.close()
        del srv
        gc.collect()            # the page pool goes before the check
        check = serve_runner._reference_check(
            family, params, records, sizes, config, serving["max_len"])
        flat = np.concatenate(check["gaps"])
        flipped = flat[flat > 0.0]
        # the numbers a limit could stand on, all in sigmas: the run's
        # own (the largest gap), and the three that use every token
        readings[name] = {
            "max": check["max"], "mean": check["mean"],
            "flipped_share": flipped.size / flat.size,
            "mean_of_flipped": float(flipped.mean()) if flipped.size else 0.0}
        check["gaps"] = [[float(g) for g in gaps] for gaps in check["gaps"]]
        row[name] = check
    return row, readings


def train_seed(ctx, seed, _with_control):
    import jax
    import numpy as np
    import deepspeed_tpu as ds
    from benchmarks.chip import model, reference, traffic, train_runner
    cell, args, family, config = ctx
    mix = traffic.resolve(cell.traffic, args.rehearse)
    sizes = family.sizes(config, args.rehearse)
    chips = len(jax.devices())
    seq = mix["seq"]
    micro = mix.get("micro_per_chip", config.get("micro_per_chip"))
    engine, _, _, _ = ds.initialize(
        model=family.build(config, args.rehearse),
        loss_fn=family.train_loss(config), rng=model.prng_key(seed),
        sample_batch={"input_ids": np.zeros((1, seq), np.int32)},
        config=train_runner._engine_config(config, micro, chips))
    rng = np.random.default_rng(seed)
    for _ in range(args.steps):
        loss = engine.train_batch(traffic.train_batch(
            rng, micro * chips, seq, sizes["vocab_size"]))
    jax.block_until_ready(loss)
    groups = train_runner.check_rows(sizes, seq, chips, seed)
    got = np.array([float(engine.eval_batch({"input_ids": g}))
                    for g in groups])
    params = train_runner.engine_params(engine)
    losses = {"engine": got, "float32": train_runner.reference_losses(
        family, params, groups, sizes, config)}
    for kind in ("int8", "float8_e4m3fn"):
        losses[kind] = train_runner.reference_losses(
            family, reference.lower_weights(params, kind), groups, sizes,
            config)
    engine.destroy()
    row = {"seed": seed, "steps": args.steps,
           "losses": {k: [float(x) for x in v] for k, v in losses.items()}}
    # the run's own number (the rms over the groups), and what it took
    # the place of: one group's difference, which can read near zero
    return row, {k: {"rms": train_runner.rms(v - losses["float32"]),
                     "first_group": abs(float(v[0] - losses["float32"][0]))}
                 for k, v in losses.items() if k != "float32"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first", type=int, default=0,
                    help="the first seed's number: an engine's memory is "
                         "not all given back in one process, so many seeds "
                         "of a training cell are several calls")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from benchmarks.chip import manifest
    cell = manifest.Cell(ROOT, manifest.load(ROOT), args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips}")

    import jax
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    from benchmarks.chip import families
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("control.py: no TPU (--rehearse runs the tiny stand-in, which "
              "separates nothing)", file=sys.stderr)
        return 2
    configure_compile_cache()
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    config = cell.config
    one_seed = {"serve": serve_seed, "train": train_seed}[config["kind"]]
    ctx = (cell, args, families.load(config), config)
    rows, readings = [], []
    for i in range(args.first, args.first + args.seeds):
        row, reading = one_seed(ctx, BASE_SEED + 104729 * i,
                                i < args.control_seeds)
        rows.append(row)
        readings.append(reading)
        print(json.dumps({"seed": row["seed"], **reading}), flush=True)
    for arm in sorted({k for r in readings for k in r}):
        for stat in readings[0][arm]:
            got = [r[arm][stat] for r in readings if arm in r]
            print(f"{arm} {stat}: smallest {min(got):.3e}, largest "
                  f"{max(got):.3e} over {len(got)} seeds", flush=True)
    if not args.rehearse:
        out = os.path.join(ROOT, "chiprun_out", "control")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(
                out, f"{args.workload}-{args.first}.json"), "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
