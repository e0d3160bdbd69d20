"""What a band around a float32 router's near-ties costs, and what it
leaves of the control: the readings ``CARRIED_TIE`` of
``families/lfm2.py`` and ``families/deepseek_v3.py`` is set between
(PERF.md section 2). One process, no timed window, the cell's own size.

Over whole sequences of the cell's mix (prompt and output lengths as
served, the output's tokens drawn from the seed) the float32 reference
gives each position's closest router choice (``near_ties="gaps"``), and
``family.unjudged`` - the function ``correct`` masks by - says for each
candidate band which positions go unjudged: their share is the band's
cost.

``--control N``: the cell's control under the same bands - the mix's
first four requests served by the program with bf16 activations (the
nearest precision below the configuration's float32) on ``N`` of the
seeds, judged by ``serve_runner._reference_check`` with every row kept,
then masked by each band: the tokens over the limit that remain.

What it does not read is how often a sound choice falls the other way:
two compilations of the reference set against each other over 531,309
positions parted nowhere (PR 54, twice), so the lower reading of the
band is the failures on record, not this tool's.

    chiprun -- python3 benchmarks/chip/tools/near_tie_probe.py \\
        --workload serve-kanana-docqa --seeds 3 --sequences 16 --control 2
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

BASE_SEED = 2147700001      # past 2**31; none of the cells' or tools'
BANDS = (0.0, 3e-5, 1e-4, 3e-4)     # candidates for CARRIED_TIE


def masks(family, choice, sizes):
    """``{band: unjudged [n]}`` by the family's own rule, for each
    candidate ``CARRIED_TIE`` (0: the near-ties alone, as before PR 54)."""
    import numpy as np
    kept, out = family.CARRIED_TIE, {}
    try:
        for band in BANDS:
            family.CARRIED_TIE = band
            out[band] = np.asarray(family.unjudged(choice[None], sizes))[0]
    finally:
        family.CARRIED_TIE = kept
    return out


def probe_seed(ctx, seed, control):
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu as ds
    from benchmarks.chip import model, serve_runner, traffic
    cell, args, family, config = ctx
    mix = traffic.resolve(cell.traffic, args.rehearse)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    width, tol = serving["max_len"], serve_runner.LOGIT_TOL_SIGMA
    module = family.build(config, args.rehearse)
    params = model.seeded_params(module, seed)
    stream = traffic.RequestStream(mix, seed, sizes["vocab_size"])
    specs = [stream.take() for _ in range(args.sequences)]
    rng = np.random.default_rng(seed + 11)

    gaps_of = serve_runner._choice_gaps(family, params, sizes, config, width)
    out = {"seed": seed, "sequences": []}
    unjudged = {band: 0 for band in BANDS}
    positions = 0
    for spec in specs:
        prompt = np.asarray(spec["prompt"], np.int32)
        n = min(len(prompt) + spec["max_new_tokens"], width)
        choice = gaps_of(np.concatenate([prompt, rng.integers(
            1, sizes["vocab_size"], size=n - len(prompt),
            dtype=np.int32)]))
        positions += n
        for band, mask in masks(family, choice, sizes).items():
            unjudged[band] += int(mask.sum())
        out["sequences"].append({
            "request": spec.get("id"), "positions": int(n),
            "near_ties": int((choice < family.NEAR_TIE).sum()),
            "choices_under": {str(b): int((choice < b).sum())
                              for b in (1e-5, 3e-5, 1e-4, 3e-4, 1e-3)}})
    out.update(positions=positions, unjudged_share_by_band={
        str(b): v / positions for b, v in unjudged.items()})
    if control:
        records = [serve_runner.Record(spec, 0.0)
                   for spec in specs[:serve_runner.CHECKED_REQUESTS]]
        lower = family.build(dict(config, compute_dtype="bfloat16"),
                             args.rehearse)
        srv = ds.init_inference(lower, params=params,
                                dtype=jnp.bfloat16).serve(dict(serving))
        for rec in records:
            serve_runner.submit(srv, rec)
        srv.run()
        srv.close()
        del srv
        gc.collect()
        near_tie, family.NEAR_TIE = family.NEAR_TIE, -1.0   # every row kept
        try:
            check = serve_runner._reference_check(
                family, params, records, sizes, config, width)
        finally:
            family.NEAR_TIE = near_tie
        over = {band: 0 for band in BANDS}
        for rec, sigmas in zip(records, check["gaps"]):
            prompt = np.asarray(rec.spec["prompt"], np.int32)
            ids = np.concatenate([prompt, np.asarray(
                rec.handle.output_tokens, np.int32)])
            rows = slice(len(prompt) - 1, len(ids) - 1)
            for band, mask in masks(family, gaps_of(ids), sizes).items():
                over[band] += int((sigmas[~mask[rows]] > tol).sum())
        out["bf16_activations"] = {
            "tokens": check["tokens"], "largest_sigma": check["max"],
            "over_the_limit_every_row_kept": int(sum(
                (g > tol).sum() for g in check["gaps"])),
            "over_the_limit_by_band": {str(b): v for b, v in over.items()}}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--sequences", type=int, default=16)
    ap.add_argument("--control", type=int, default=0,
                    help="as many of the seeds also serve the control")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "near_tie_probe"))
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from benchmarks.chip import families, manifest
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("near_tie_probe: not on a tpu (--rehearse runs the CPU "
              "stand-in)", file=sys.stderr)
        return 2
    configure_compile_cache()
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(ROOT, manifest.load(ROOT), args.workload)
    family = families.load(cell.config)
    if not hasattr(family, "NEAR_TIE"):
        print(f"near_tie_probe: {family.__name__} leaves no near-tie "
              "unjudged", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    ctx = (cell, args, family, cell.config)
    for k in range(args.seeds):
        seed = BASE_SEED + 7919 * k
        out = probe_seed(ctx, seed, k < args.control)
        with open(os.path.join(args.out, f"{cell.name}-{seed}.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
        print(f"seed {seed}: " + json.dumps(
            {k: v for k, v in out.items()
             if k not in ("seed", "sequences")}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
