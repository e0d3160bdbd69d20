"""What ``correct`` cannot say of the cell ``serve-kanana-docqa``, read on
the chip at the configuration's size, one process, no timed window
(PERF.md section 2, PR 37). ``correct`` sees tokens; this tool sees the
logits the tokens were sampled from, through the paged path as
configured:

- a first request publishes a document of ``--doc-pages`` whole pages
  (64, the cell's 8,192 tokens). It is admitted alone, so the server
  takes its prefill as chunks of four pages;
- of the three served after it one opens with that document — a prefix
  hit on its latent pages, one chunk of its own over the 8 k shared
  positions — and two are unshared (450 tokens, which goes as a chunk of
  three pages' rows in the four-page program or page by page as the
  server chooses, and 100).

Every served position's row of logits is set against the float32
reference's full forward pass over prompt + output
(``families/deepseek_v3.py``, near-ties flagged): the largest of the
row's differences in standard deviations of the reference's row. A
request reads two numbers, the largest such difference over its
positions and their mean, held to ``ROW_TOL_SIGMA`` and
``ROW_MEAN_TOL_SIGMA``; a position where one of the reference's own
routers chose on a near-tie is counted and left out of both.

The same is read of what has to be refused, with the same weights:

- a broken cache, served: a second document is published beside the
  first and its latent pages are copied over the first's before the
  second round, so the prefix hit reads another document's pages
  (judged on the request that hits);
- six wrong models (``WRONG``): the rows served as configured against a
  reference with ``routed_scaling_factor`` 1, a softmax in the sigmoid's
  place, the chosen experts weighed by their bias-corrected scores, no
  shared expert, five experts a token, and rotary pairs left
  interleaved (judged on the requests of up to 1,024 tokens: a wrong
  model is wrong at every length, and a reference of 8 k positions is
  compiled once, not seven times);
- the control in the nearest precision below the configuration's bf16
  weights: the reference itself with its matrices rounded to fp8 (e4m3),
  a scale a channel (``reference.lower_weights``; routers and their bias
  stay float32, as a weight-only deployment keeps them), and the same
  with int8, reported beside it.

    chiprun -- python3 benchmarks/chip/tools/kanana_check.py \\
        [--config kanana-2-30b-a3b-serve] [--seeds N,N,...] [--rehearse]

Writes ``<--out, default chiprun_out/kanana_check>/<seed>.json``."""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

BODIES = (60, 3, 450, 100)      # the publisher's, the hit's, two unshared
SHORT = 1024                    # the wrong models are judged up to here
# between the largest reading of the program as configured and the
# smallest of what has to be refused (my chip runs, PR 37; PERF.md
# section 2 has both readings of each). As configured a request of up to
# 450 tokens reads 8e-6 | 6e-6, and one over a document of 8,192 tokens
# 2.2e-3 | 1.1e-3: its prompt holds ~40 positions where the reference's
# sixth and seventh scores lie under 1e-5 apart, float32's rounding
# settles one or two of them the other way in the program, and every
# later position attends that position's vector at a share of one in
# 8,000 (with no expert layer the same request reads 9e-6: the probe of
# PERF.md section 6). What has to be refused reads 1.5 and up | 0.31 for a
# run's worst request (the bias in the weights; 0.19 for the least of any
# wrong request). Each limit is near the geometric middle of its pair. A
# served row at one of the reference's own near-ties (row_gaps) is left
# out: the one such row of the first run read 1.18
ROW_TOL_SIGMA = 5e-2
ROW_MEAN_TOL_SIGMA = 1.5e-2
LOWERED = ("float8_e4m3fn", "int8")


def _softmax_scores(m, p):
    import jax
    scores = jax.nn.softmax(m @ p["router"], -1)
    return scores, scores + p["expert_bias"]


def _weigh_biased(m, p, top_k, config):
    """``families/deepseek_v3.py _route`` with the bias in the weights."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.families import deepseek_v3 as family
    _, biased = family._scores(m, p)
    weight, chosen = jax.lax.top_k(biased, top_k)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * config["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, biased.shape[-1])
                   * weight[..., None], axis=-2)


# name -> (sizes the reference is given in place of the configuration's,
# keys of the configuration likewise, functions of the family replaced)
WRONG = {
    "scaling_factor_1": ({}, {"routed_scaling_factor": 1.0}, {}),
    "softmax_for_sigmoid": ({}, {}, {"_scores": _softmax_scores}),
    "bias_in_the_weights": ({}, {}, {"_route": _weigh_biased}),
    "no_shared_expert": ({"n_shared_experts": 0}, {}, {}),
    "top_5": ({"num_experts_per_tok": 5}, {}, {}),
    "rotary_pairs_left_interleaved": ({}, {"rope_interleave": False}, {}),
}


def reference_rows(family, params, prompt, output, sizes, config, wrong=None,
                   lower=None, flagged=False):
    """The reference's rows at the positions that predict ``output``:
    one full forward pass over ``prompt + output`` (padded to a whole
    block of queries, which a causal model's earlier rows do not see),
    near-ties kept. ``wrong`` names an entry of ``WRONG``. ``flagged``:
    also which of those rows' positions hold a near-tie of one of the
    reference's routers, and how many of the prompt's positions do."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import reference
    over_sizes, over_config, patches = WRONG[wrong] if wrong else ({}, {}, {})
    ids = np.concatenate([prompt, output]).astype(np.int32)
    padded = np.zeros(-(-len(ids) // family.QUERY_BLOCK)
                      * family.QUERY_BLOCK, np.int32)
    padded[:len(ids)] = ids
    kept = {name: getattr(family, name) for name in patches}
    for name, fn in patches.items():
        setattr(family, name, fn)
    try:
        with reference.highest():
            rows, gap = jax.jit(lambda p, x: family.reference_logits(
                p, x, dict(sizes, **over_sizes), dict(config, **over_config),
                lower, near_ties="gaps"))(params, jnp.asarray(padded[None]))
            served = slice(len(prompt) - 1, len(ids) - 1)
            rows = np.asarray(rows[0, served])
            near = np.asarray(gap[0]) < family.NEAR_TIE
            if flagged:
                return rows, near[served], int(near[:len(prompt) - 1].sum())
            return rows
    finally:
        for name, fn in kept.items():
            setattr(family, name, fn)


def lowered(kind):
    """Weight-only: routers and their bias stay float32."""
    from benchmarks.chip import reference

    def lower(tree):
        low = reference.lower_weights(tree, kind)
        if "moe" in tree:
            low["moe"] = dict(low["moe"], router=tree["moe"]["router"])
        return low
    return lower


def swap_pages(srv, onto, other):
    """The latent pages ``other`` copied over the pages ``onto``, in
    every layer's leaf of the server's pool."""
    import jax
    import numpy as np
    mgr = srv._paged
    onto, other = np.asarray(onto), np.asarray(other)
    mgr.pool = jax.tree.map(
        lambda leaf: leaf.at[onto].set(leaf[other])
        if getattr(leaf, "ndim", 0) == 4 and leaf.shape[0] == mgr.num_pages
        else leaf, mgr.pool)


def serve(module, params, config, serving, seen, first, rest, new_tokens,
          other_document=None):
    """``first`` served alone (it publishes its whole pages), then
    ``rest`` together. With ``other_document`` that prompt is served
    after ``first`` and its pages are copied over ``first``'s shared
    ones in between. Returns ``[(prompt, handle, rows)]``, the tokens
    the round reused and the pages a chunk took at most."""
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability.metrics import get_registry
    from benchmarks.chip.tools.lfm2_check import DispatchLog
    srv = ds.init_inference(
        module, params=params,
        dtype=getattr(jnp, config["compute_dtype"])).serve(dict(serving))
    logged = DispatchLog(srv, seen)
    count = lambda name: get_registry().counter("serving/" + name).value
    before = count("prefill_chunks"), count("prefill_chunk_pages")
    handles = [srv.submit(first, max_new_tokens=new_tokens)]
    srv.run()
    widest = (count("prefill_chunk_pages") - before[1]) \
        / max(1, count("prefill_chunks") - before[0])
    if other_document is not None:
        # one token more than its whole pages: a lookup leaves a
        # prompt's last token to be computed
        import numpy as np
        beside = np.append(other_document, np.int32(1))
        srv.submit(beside, max_new_tokens=1)
        srv.run()
        page = serving["paging"]["page_len"]
        onto = srv._paged.prefix.match(first)
        other = srv._paged.prefix.match(beside)
        assert len(onto) == len(other) == len(other_document) // page
        swap_pages(srv, onto, other)
    reused = srv.metrics.prefill_tokens_reused
    handles += [srv.submit(p, max_new_tokens=new_tokens) for p in rest]
    srv.run()
    out = [(p, h, logged.rows(h)) for p, h in zip([first] + rest, handles)]
    reused = srv.metrics.prefill_tokens_reused - reused
    srv.close()
    del srv, logged
    gc.collect()
    return out, reused, widest


def row_gaps(rows, want, near=None):
    """Served rows of logits ``[n, V]`` against the reference's: the
    largest difference of each row in sigmas of the reference's row,
    then its largest and its mean over the positions. ``near`` (``[n]``
    bool) names the rows the reference chose on a near-tie of a router
    (``families/deepseek_v3.py NEAR_TIE``): float32's rounding decides
    such a choice, so they are left out and counted."""
    import numpy as np
    diff = np.abs(rows - want).max(-1) / want.std(-1)
    judged = diff if near is None or near.all() else diff[~near]
    out = {"max_diff_sigma": float(judged.max()),
           "mean_diff_sigma": float(judged.mean()),
           "argmax_agree": int((rows.argmax(-1) == want.argmax(-1)).sum())}
    if near is not None:
        out.update(near_tie_rows=int(near.sum()),
                   largest_near_tie_row=float(
                       diff[near].max() if near.any() else 0.0),
                   per_row=[float(x) for x in diff])
    return out


def reading(requests):
    out = {"requests": requests,
           "row_max": max(r["max_diff_sigma"] for r in requests),
           "row_mean": max(r["mean_diff_sigma"] for r in requests),
           "row_mean_least": min(r["mean_diff_sigma"] for r in requests)}
    out["within"] = (out["row_max"] <= ROW_TOL_SIGMA
                     and out["row_mean"] <= ROW_MEAN_TOL_SIGMA)
    return out


def check_seed(args, config, seed, seen, watched):
    import numpy as np
    from benchmarks.chip import families, model
    from deepspeed_tpu.serving.paging import manager

    family = families.load(config)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    page = serving["paging"]["page_len"]
    doc_pages = min(args.doc_pages,
                    (serving["max_len"] - max(BODIES[:2]) - args.new) // page)
    module = family.build(config, args.rehearse)
    params = model.seeded_params(module, seed)
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(1, sizes["vocab_size"], size=n,
                                  dtype=np.int32)
    document, other = draw(doc_pages * page), draw(doc_pages * page)
    first = np.concatenate([document, draw(BODIES[0])])
    rest = [np.concatenate([document, draw(BODIES[1])])] + [
        draw(n) for n in BODIES[2:]
        if n + args.new <= serving["max_len"]][:serving["num_slots"] - 1]

    def wanted(prompt, handle, **how):
        return reference_rows(family, params, prompt,
                              np.asarray(handle.output_tokens), sizes,
                              config, **how)

    def served(other_document=None):
        sample, manager._sample_impl = manager._sample_impl, watched
        try:
            return serve(module, params, config, serving, seen, first, rest,
                         args.new, other_document)
        finally:
            manager._sample_impl = sample

    out = {"seed": seed, "new_tokens": args.new, "document_pages": doc_pages,
           "limits": {"row_max": ROW_TOL_SIGMA,
                      "row_mean": ROW_MEAN_TOL_SIGMA}}
    sound, reused, widest = served()
    assert reused == doc_pages * page, reused         # the hit happened
    out["publisher_chunk_pages_mean"] = widest
    flagged = [wanted(p, h, flagged=True) for p, h, _ in sound]
    want = [w for w, _, _ in flagged]
    out["as_configured"] = reading([
        dict(row_gaps(rows, w, near), prompt_len=len(p), hit=i == 1,
             near_ties_in_prompt=before)
        for i, ((p, _, rows), (w, near, before))
        in enumerate(zip(sound, flagged))])
    broken, reused, _ = served(other)
    assert reused == doc_pages * page, reused
    p, h, rows = broken[1]                             # the one that hits
    out["prefix_hit_on_another_documents_pages"] = reading([
        dict(row_gaps(rows, wanted(p, h)), prompt_len=len(p), hit=True)])
    short = [(p, h, rows, w) for (p, h, rows), w in zip(sound, want)
             if len(p) <= SHORT] or [sound[0] + (want[0],)]
    for name in WRONG:
        out[name] = reading([
            dict(row_gaps(rows, wanted(p, h, wrong=name)), prompt_len=len(p))
            for p, h, rows, _ in short])
    for kind in LOWERED:
        out[kind] = reading([
            dict(row_gaps(wanted(p, h, lower=lowered(kind)), w),
                 prompt_len=len(p)) for p, h, _, w in short])
    for name, arm in out.items():
        if isinstance(arm, dict) and "within" in arm:
            print(f"seed {seed} {name}: " + json.dumps(
                {f: arm[f] for f in arm if f != "requests"}), flush=True)
    return out


REFUSED = ("prefix_hit_on_another_documents_pages",) + tuple(WRONG) \
    + ("float8_e4m3fn",)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="kanana-2-30b-a3b-serve",
                    help="a file of benchmarks/chip/configs, by name")
    ap.add_argument("--seeds", default="2147640037")
    ap.add_argument("--new", type=int, default=33,
                    help="tokens a request generates: one from its prefill "
                         "and --new - 1 decode steps")
    ap.add_argument("--doc-pages", type=int, default=64,
                    help="whole pages of the shared document (cut to what "
                         "a slot holds)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kanana_check"))
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmarks.chip import manifest
    from deepspeed_tpu.serving.paging import manager
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("kanana_check: not on a tpu (--rehearse runs the CPU "
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
        sound = sound and out["as_configured"]["within"]
        passed = [name for name in REFUSED + ("int8",)
                  if out[name]["within"]]
        print(f"seed {seed}: as configured "
              f"{'within' if out['as_configured']['within'] else 'OVER'} "
              f"the limits ({ROW_TOL_SIGMA} / {ROW_MEAN_TOL_SIGMA} sigma); "
              f"publisher's chunks {out['publisher_chunk_pages_mean']:.2f} "
              f"pages wide in the mean; not refused: {passed or 'none'}",
              flush=True)
        gc.collect()
    # a rehearsal at 64 wide holds the tool together, not the limits
    return 0 if sound or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
