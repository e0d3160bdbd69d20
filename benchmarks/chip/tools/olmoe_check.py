"""What ``correct`` cannot say of the OLMoE cell, read on the chip at the
cell's size, one process, no timed window (PERF.md section 2, PR 28).

``correct`` sees tokens: each served token within ``LOGIT_TOL_SIGMA`` of
its position's best float32 reference logit. That refuses another
model's tokens and renormalised top-k weights, and does not tell top-7
experts a token from top-8. This tool sees the logits the tokens were
sampled from. A seeded sample of requests is served as configured —
prompts prefilled in chunks of a page, then ``--new`` tokens decoded
through the page pool by the paged-attention kernel — while the
programs' sampler is watched (the one change: a host callback that
copies the logits out). Every served position's row of logits is set
against the float32 reference's full forward pass over prompt + output:
the largest of the row's differences, in standard deviations of the
reference's row. A request reads two numbers, the largest such
difference over its positions and their mean, held to ``ROW_TOL_SIGMA``
and ``ROW_MEAN_TOL_SIGMA``.

The same is read of what has to be refused, with the same weights:

- two wrong paths, served: top-(k-1) experts a token, and the k weights
  renormalised (each also judged by the run's own comparison of tokens,
  ``serve_runner._reference_check``, which refuses it or is reported as
  not telling it apart);
- two controls in the nearest precisions below the configuration's
  bf16: the reference itself with its matrices rounded to fp8 (e4m3)
  and to int8, a scale a channel (``reference.lower_weights``; the
  routers stay float32, as a weight-only deployment keeps them).

Both limits lie between the largest reading of the program as
configured and the smallest of those four (beside the constants below;
PERF.md section 2; my chip runs, PR 28). The mean is the sturdier: it
parts the program from the nearest wrong request by 2.1x, the largest
difference by 1.25x.

    chiprun -- python3 benchmarks/chip/tools/olmoe_check.py \
        [--workload serve-olmoe-longgen] [--seeds N,N,...] [--rehearse]

Writes ``<--out, default chiprun_out/olmoe_check>/<seed>.json``."""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

PROMPTS = (60, 150, 300, 450)       # one to four chunks of 128
# between the largest reading of the program as configured and the
# smallest of what has to be refused (five runs on three seeds | three
# seeds x four arms; four requests a run; my chip runs, PR 28): a
# request's largest row difference 0.090-0.133 | 0.166 (int8 weights),
# 0.171 (top-7) and up, the largest of a run's four requests 0.190 and
# up; its mean over the positions 0.034-0.057 | 0.121 (int8), 0.139
# (top-7) and up
ROW_TOL_SIGMA = 0.16
ROW_MEAN_TOL_SIGMA = 0.085
CONTROLS = ("float8_e4m3fn", "int8")


def serve(module, params, config, serving, prompts, new_tokens):
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from benchmarks.chip import serve_runner
    srv = ds.init_inference(
        module, params=params,
        dtype=getattr(jnp, config["compute_dtype"])).serve(dict(serving))
    records = [serve_runner.Record(
        {"prompt": p, "max_new_tokens": new_tokens}, 0.0) for p in prompts]
    for rec in records:
        serve_runner.submit(srv, rec)
    srv.run()
    srv.close()
    del srv
    gc.collect()
    return records


def watched_logits(seen, records, page, slots, new_tokens):
    """Per request the ``[new_tokens, V]`` logits its tokens were sampled
    from: requests take slots 0, 1, ... and prefill one chunk an
    iteration in that order; a request's decode logits are its slot's
    row of the decode dispatches after its last chunk (as
    ``tests/unit/test_olmoe.py`` reads them on the CPU)."""
    import numpy as np
    out, chunk_no = [], 0
    chunk_at = [i for i, x in enumerate(seen) if x.shape[0] == 1]
    for slot, rec in enumerate(records):
        chunk_no += -(-len(rec.spec["prompt"]) // page)
        last = chunk_at[chunk_no - 1]
        decode = [x for x in seen[last + 1:] if x.shape[0] == slots]
        rows = [seen[last][0]] + [x[slot] for x in decode[:new_tokens - 1]]
        assert [int(r.argmax()) for r in rows] \
            == list(rec.handle.output_tokens), "rows are not this request's"
        out.append(np.stack(rows))
    return out


def row_gaps(rows, want):
    """Served rows of logits ``[n, V]`` against the reference's: the
    largest difference of each row in sigmas of the reference's row,
    then its largest and its mean over the positions."""
    import numpy as np
    diff = np.abs(rows - want).max(-1) / want.std(-1)
    return {"max_diff_sigma": float(diff.max()),
            "mean_diff_sigma": float(diff.mean()),
            "last_position_diff_sigma": float(diff[-1]),
            "argmax_agree": int((rows.argmax(-1) == want.argmax(-1)).sum())}


def reading(requests):
    """What an arm reads: the largest of its requests' two numbers, and
    whether both are inside the limits."""
    out = {"requests": requests,
           "row_max": max(r["max_diff_sigma"] for r in requests),
           "row_mean": max(r["mean_diff_sigma"] for r in requests),
           "row_mean_least": min(r["mean_diff_sigma"] for r in requests)}
    out["within"] = (out["row_max"] <= ROW_TOL_SIGMA
                     and out["row_mean"] <= ROW_MEAN_TOL_SIGMA)
    return out


def check_seed(args, cell, seed, seen, watched):
    """``watched`` is the sampler that copies its logits into ``seen``:
    one for the whole process, because a compiled program keeps the
    callback it was traced with."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import families, model, reference, serve_runner
    from deepspeed_tpu.serving.paging import manager

    config, family = cell.config, families.load(cell.config)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    page, slots = serving["paging"]["page_len"], serving["num_slots"]
    params = model.seeded_params(family.build(config, args.rehearse), seed)
    rng = np.random.default_rng(seed)
    lengths = [n for n in PROMPTS if n + args.new <= serving["max_len"]]
    prompts = [rng.integers(1, sizes["vocab_size"], size=n, dtype=np.int32)
               for n in lengths[:slots]]

    def lowered(kind):
        # weight-only: a layer's router stays as it is
        def lower(tree):
            low = reference.lower_weights(tree, kind)
            if "mlp" in tree:
                low["mlp"] = dict(low["mlp"], router=tree["mlp"]["router"])
            return low
        return lower

    def forward(lower=None):
        return jax.jit(lambda p, ids: family.reference_logits(
            p, ids, sizes, config, lower))
    plain = forward()

    def wanted(rec, fn=plain):
        """The reference's rows at the positions ``rec``'s tokens were
        sampled from, teacher-forced on those tokens."""
        prompt = rec.spec["prompt"]
        ids = np.concatenate([prompt, rec.handle.output_tokens])
        with reference.highest():
            return np.asarray(fn(params, jnp.asarray(ids[None]))[0])[
                len(prompt) - 1:len(ids) - 1]

    out = {"seed": seed, "new_tokens": args.new,
           "limits": {"row_max": ROW_TOL_SIGMA,
                      "row_mean": ROW_MEAN_TOL_SIGMA,
                      "tokens": serve_runner.LOGIT_TOL_SIGMA}}
    k = sizes["num_experts_per_tok"]
    configured = None
    for name, override in (("as_configured", {}),
                           ("top_k_minus_1", {"num_experts_per_tok": k - 1}),
                           ("renormalised", {"norm_topk_prob": True})):
        module = family.build(config, args.rehearse, **override)
        sample, manager._sample_impl = manager._sample_impl, watched
        try:
            records = serve(module, params, config, serving, prompts,
                            args.new)
            jax.effects_barrier()
        finally:
            manager._sample_impl = sample
        rows = watched_logits(seen, records, page, slots, args.new)
        del seen[:]
        arm = reading([dict(row_gaps(got, wanted(rec)),
                            prompt_len=len(rec.spec["prompt"]))
                       for rec, got in zip(records, rows)])
        tokens = serve_runner._reference_check(
            family, params, records, sizes, config, serving["max_len"])
        arm["tokens"] = {f: tokens[f] for f in ("max", "mean", "exact",
                                                "tokens")}
        arm["tokens"]["within"] = bool(
            tokens["max"] <= serve_runner.LOGIT_TOL_SIGMA)
        out[name] = arm
        if configured is None:
            configured = records
    for kind in CONTROLS:
        low = forward(lowered(kind))
        out[kind] = reading([dict(row_gaps(wanted(rec, low), wanted(rec)),
                                  prompt_len=len(rec.spec["prompt"]))
                             for rec in configured])
    for name, arm in out.items():
        if isinstance(arm, dict) and "within" in arm:
            print(f"seed {seed} {name}: " + json.dumps(
                {f: arm[f] for f in arm if f != "requests"}), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-olmoe-longgen")
    ap.add_argument("--seeds", default="2147640001")
    ap.add_argument("--new", type=int, default=65,
                    help="tokens a request generates: one from its prefill "
                         "and --new - 1 decode steps")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "olmoe_check"))
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmarks.chip import manifest
    from deepspeed_tpu.serving.paging import manager
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("olmoe_check: not on a tpu (--rehearse runs the CPU stand-in)",
              file=sys.stderr)
        return 2
    configure_compile_cache()
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)

    cell = manifest.Cell(ROOT, manifest.load(ROOT), args.workload)
    os.makedirs(args.out, exist_ok=True)
    seen, sample = [], manager._sample_impl

    def watched(logits, *rest):
        jax.debug.callback(
            lambda x: seen.append(np.asarray(x, np.float32)), logits,
            ordered=True)
        return sample(logits, *rest)

    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = check_seed(args, cell, seed, seen, watched)
        with open(os.path.join(args.out, f"{seed}.json"), "w") as f:
            json.dump(out, f, indent=1)
        sound = sound and out["as_configured"]["within"]
        passed = [name for name in ("top_k_minus_1", "renormalised")
                  + CONTROLS if out[name]["within"]]
        print(f"seed {seed}: as configured "
              f"{'within' if out['as_configured']['within'] else 'OVER'} "
              f"the limits ({ROW_TOL_SIGMA} / {ROW_MEAN_TOL_SIGMA} sigma); "
              f"not refused: {passed or 'none'}", flush=True)
        gc.collect()
    # a rehearsal at 64 wide holds the tool together, not the limits
    return 0 if sound or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
