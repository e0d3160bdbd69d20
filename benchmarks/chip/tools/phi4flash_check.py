"""What ``correct`` cannot say of the cell ``serve-phi4flash-reason``,
read on the chip at the configuration's size, one process, no timed
window. ``correct`` sees tokens; this tool sees the logits the tokens were
sampled from, through the paged path as configured (a few slots: every
dispatch's rows come to the host): three requests together — one whose
generation takes its context past the window, so that its rings wrap,
one whose prompt already passes it, prefilled in chunks that cross the
window's end, and a short one.

Every served position's row of logits is set against the float32
reference's full forward pass over prompt + output (its mixer's
recurrence one token at a time, every layer on every position): the
largest of the row's differences, in standard deviations of the
reference's row. A request reads two numbers, the largest such
difference over its positions and their mean, held to ``ROW_TOL_SIGMA``
and ``ROW_MEAN_TOL_SIGMA``; beside them the cell's own comparison
(``serve_runner._reference_check`` and ``LOGIT_TOL_SIGMA``).

The same is read of what has to be refused, with the same weights:

- a zero-state mixer, served: every slot's SSM state zeroed once the
  prompts are in (what a prefix hit without a stored state would start
  from), judged on the rows decoded after it;
- wrong readings of the architecture, the rows served as configured
  against a reference with the memory taken after the mixer's gate,
  with lambda = 0, with a window of 511, and with the last cross layer
  reading layer 17's keys at another head pairing;
- the control in the nearest precision below the configuration's bf16:
  the reference itself with its matrices rounded to fp8 (e4m3), a scale a
  channel (``reference.lower_weights``), and the same with int8, reported
  beside it.

    chiprun -- python3 benchmarks/chip/tools/phi4flash_check.py \\
        [--config phi-4-mini-flash-serve] [--seeds N,N,...] [--rehearse]

Writes ``<--out, default chiprun_out/phi4flash_check>/<seed>.json``."""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

SLOTS = 4                       # rows a dispatch's logits come back for
# (prompt, generated): past the window by generating; a prompt past it;
# a short one. A rehearsal's are cut to its slots
REQUESTS = ((300, 330), (700, 48), (40, 48))
# between the largest reading of the program as configured and the
# smallest of what has to be refused (my chip runs, PR 56, three seeds;
# PERF.md section 2 has every reading): a request's largest row
# difference 0.049-0.061 as configured | 0.389-0.412 for the wrong head
# pairing in one cross layer of seven (0.74-1.42 for a window of 511,
# 2.79-2.93 for fp8 weights, 2.3 and up for the others); its mean over
# the positions 0.040-0.050 | 0.331-0.345 (0.38-0.54, 2.16-2.43). The
# rows were not read in bf16 activations, in which the cell's own
# comparison failed (0.1465 sigma against 0.1)
ROW_TOL_SIGMA = 0.15
ROW_MEAN_TOL_SIGMA = 0.12
WRONG = ("memory_after_gate", "lambda_zero", "window_less_one",
         "wrong_pairing")
CONTROLS = ("float8_e4m3fn", "int8")
REFUSED = ("zero_state",) + WRONG + ("float8_e4m3fn",)
REPORTED = ("as_configured",) + REFUSED + ("int8",)


def check_seed(args, config, seed, seen, watched):
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu as ds
    from benchmarks.chip import families, model, reference, serve_runner
    from benchmarks.chip.tools.lfm2_check import DispatchLog
    from deepspeed_tpu.serving.paging import manager

    family = families.load(config)
    sizes = family.sizes(config, args.rehearse)
    serving = dict(config["rehearse"]["serving"] if args.rehearse
                   else config["serving"])
    serving["num_slots"] = min(SLOTS, serving["num_slots"])
    serving["paging"] = dict(serving["paging"], num_pages=None)
    module = family.build(config, args.rehearse)
    params = model.seeded_params(module, seed)
    rng = np.random.default_rng(seed)
    room = serving["max_len"]
    requests = [(min(p, room // 2), min(n, room // 2 - 1))
                for p, n in REQUESTS][:serving["num_slots"]]
    prompts = [rng.integers(1, sizes["vocab_size"], size=p, dtype=np.int32)
               for p, _ in requests]
    dtype = jnp.float32 if args.rehearse else getattr(
        jnp, config["compute_dtype"])

    def serve(break_state):
        patch = manager._sample_impl
        manager._sample_impl = watched
        try:
            log = DispatchLog(ds.init_inference(
                module, params=params, dtype=dtype).serve(serving), seen)
            srv = log.srv
            handles = [srv.submit(p, max_new_tokens=n)
                       for p, (_, n) in zip(prompts, requests)]
            while break_state and not all(h.output_tokens for h in handles):
                srv.advance()
            if break_state:
                pool = srv._paged.pool
                for i in family.layers_of(sizes, "mixer"):
                    unit = pool[f"layers_{i}"]["mixer"]
                    unit["ssm_state"] = jnp.zeros_like(unit["ssm_state"])
            srv.run()
            rows = [log.rows(h) for h in handles]
            srv.close()
        finally:
            manager._sample_impl = patch
        gc.collect()
        return handles, rows

    def forward(wrong=None, lower=None):
        with reference.highest():
            return jax.jit(lambda p, ids: family.reference_logits(
                p, ids, sizes, config, wrong=wrong, lower=lower)[0])

    def reading(handles, rows, fn, skip=0):
        """Each request's largest and mean row difference, and the
        largest and the mean over the requests."""
        worst, means = [], []
        # one width for every request (a causal model: the padding after
        # a request's tokens moves none of its rows), so that a variant
        # of the reference compiles once
        width = -(-max(p + n for p, n in requests) // 128) * 128
        for prompt, handle, got in zip(prompts, handles, rows):
            ids = np.concatenate([prompt, np.asarray(handle.output_tokens)])
            padded = np.zeros((1, width), np.int32)
            padded[0, :len(ids)] = ids
            with reference.highest():
                want = fn(params, jnp.asarray(padded))
            want = want[len(prompt) - 1:len(ids) - 1]
            # each row's largest difference in sigmas of the reference's
            gaps = np.asarray(jnp.abs(jnp.asarray(got) - want).max(-1)
                              / want.std(-1))[skip:]
            worst.append(float(gaps.max()))
            means.append(float(gaps.mean()))
        return {"row_max": max(worst), "row_mean": max(means),
                "requests": [{"row_max": w, "row_mean": m}
                             for w, m in zip(worst, means)],
                "within": max(worst) <= ROW_TOL_SIGMA
                and max(means) <= ROW_MEAN_TOL_SIGMA}

    out = {"seed": seed, "requests": requests,
           "limits": {"row_max": ROW_TOL_SIGMA,
                      "row_mean": ROW_MEAN_TOL_SIGMA}}
    handles, rows = serve(False)
    sound = forward()
    out["as_configured"] = reading(handles, rows, sound)
    recs = [types.SimpleNamespace(spec={"prompt": p}, handle=h)
            for p, h in zip(prompts, handles)]
    check = serve_runner._reference_check(
        family, params, recs, sizes, config, serving["max_len"])
    out["as_configured"]["the_cells_own"] = {
        "logit_gap_sigma": check["max"], "tokens": check["tokens"],
        "exact": check["exact"],
        "correct": check["max"] <= serve_runner.LOGIT_TOL_SIGMA}
    for wrong in WRONG:
        out[wrong] = reading(handles, rows, forward(wrong=wrong))
    for kind in CONTROLS:
        out[kind] = reading(handles, rows, forward(
            lower=lambda tree, k=kind: reference.lower_weights(tree, k)))
    del rows
    gc.collect()
    handles, rows = serve(True)
    # the first token of each came from its prompt's own state
    out["zero_state"] = reading(handles, rows, sound, skip=1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi-4-mini-flash-serve",
                    help="a file of benchmarks/chip/configs, by name")
    ap.add_argument("--seeds", default="2147640056")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "phi4flash_check"))
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmarks.chip import manifest
    from deepspeed_tpu.serving.paging import manager
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("phi4flash_check: not on a tpu (--rehearse runs the CPU "
              "stand-in)", file=sys.stderr)
        return 2
    configure_compile_cache()
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)

    config = manifest.load_json(os.path.join(
        manifest.bench_dir(ROOT, manifest.load(ROOT)), "configs",
        args.config + ".json"))
    os.makedirs(args.out, exist_ok=True)
    seen, sample = [], manager._sample_impl

    def watched(logits, *rest):
        jax.debug.callback(
            lambda x: seen.append(np.asarray(x, np.float32)), logits,
            ordered=True)
        return sample(logits, *rest)

    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = check_seed(args, config, seed, seen, watched)
        with open(os.path.join(args.out, f"{seed}.json"), "w") as f:
            json.dump(out, f, indent=1)
        passed = [name for name in REFUSED if out[name]["within"]]
        sound = sound and not passed and out["as_configured"]["within"] \
            and out["as_configured"]["the_cells_own"]["correct"]
        print(f"seed {seed}: as configured "
              f"{'within' if out['as_configured']['within'] else 'OVER'} "
              f"the limits ({ROW_TOL_SIGMA} / {ROW_MEAN_TOL_SIGMA} sigma), "
              f"the cell's own gap "
              f"{out['as_configured']['the_cells_own']['logit_gap_sigma']:.4f}"
              f"; not refused: {passed or 'none'}; row_max | row_mean: "
              + ", ".join(f"{name} {out[name]['row_max']:.4f} | "
                          f"{out[name]['row_mean']:.4f}"
                          for name in REPORTED), flush=True)
        gc.collect()
    # a rehearsal at 64 wide holds the tool together, not the limits
    return 0 if sound or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
