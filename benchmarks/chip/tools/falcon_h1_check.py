"""What ``correct`` cannot say of the cell ``serve-falconh1-chat``, read on
the chip at the configuration's size, one process, no timed window
(PERF.md section 2, PR 44). ``correct`` sees tokens; this tool sees the
logits the tokens were sampled from, through the paged path as
configured, with every way a request can start among them:

- a first request served alone, **cold**: it opens with a system prompt
  of ``SHARED_PAGES`` whole pages, publishes its pages and leaves a
  snapshot at its own last whole page (its leaf);
- a second that shares the system prompt and parts ways at its last page,
  whose end has no snapshot: **a hit shortened** to nothing
  (``serving/state_restore_missed``), which computes its whole prompt and
  takes the snapshot as it passes;
- then three together: one that shares the system prompt and **restores
  that snapshot**, the first one's prompt again (**a hit at the leaf**),
  and one **unshared**;
- then more unshared prompts of a page and a few tokens than the snapshot
  pool has entries, each leaving its leaf, and the first one's prompt a
  third time: its pages are still the prefix cache's, every snapshot
  under them is **evicted**, and the hit is shortened to nothing.

Every served position's row of logits is set against the float32
reference's full forward pass over prompt + output (its recurrence one
token at a time): the largest of the row's differences, in standard
deviations of the reference's row. A request reads two numbers, the
largest such difference over its positions and their mean, held to
``ROW_TOL_SIGMA`` and ``ROW_MEAN_TOL_SIGMA``.

Beside the rows, each arm whose tokens were served reads **the cell's own
comparison** (``serve_runner._reference_check`` and ``LOGIT_TOL_SIGMA``,
called as ``run.py`` calls them): the served tokens' largest gap under the
reference's best logit, and ``correct`` as a run of the cell would say it.

The same is read of what has to be refused, with the same weights:

- a broken cache, served: the snapshots are zeroed before the third
  round, so both hits start from zero state (judged on the requests that
  hit);
- the configuration's float32 state kept in bf16, served
  (``ssm_state_dtype``: slots and snapshots both): reported, and refused
  only on the one float32 mixer below, because under bf16 products a
  state rounded to bf16 at every step reads what the float32 state reads
  at these lengths;
- wrong models: the rows served as configured against a reference with
  ``ssm_out_multiplier``, ``key_multiplier`` or ``mlp_multipliers[1]``
  dropped, with the gated norm before the gate, and with B and C read
  from the other group (this one through the cell's comparison too);
- the state's path alone, in float32 (``mixer_alone``): ONE mixer at the
  published widths, a chunk of two pages from zero state, then
  ``MIXER_TOKENS`` decode tokens through the update kernel over eight
  rows of which three decode, against the family's per-token recurrence
  — as it is, with the chunk's state dropped before the decode tokens,
  with its state kept in bf16, and against the other group's B and C —
  held to ``MIXER_TOL_SIGMA``;
- the control in the nearest precision below the configuration's bf16:
  the reference itself with its matrices rounded to fp8 (e4m3), a scale a
  channel (``reference.lower_weights``; the mixer's vectors, taps and
  norms stay float32, as a weight-only deployment keeps them), and the
  same with int8, reported beside it.

**Why the state shows in a bf16 logit** (PERF.md section 2): the mixer's
in-projection seeds B's and C's columns so that both arrive at unit
variance behind the muP factors (``models/layers.py
_mixer_in_proj_init``), and the recurrent part is ~40% of the mixer's
output. At fan-in scale it was ~1% of it, under bf16's rounding of a
logit, and a hit from zero state read what the sound program read.

    chiprun -- python3 benchmarks/chip/tools/falcon_h1_check.py \\
        [--config falcon-h1-34b-9l-serve] [--seeds N,N,...] [--rehearse]

Writes ``<--out, default chiprun_out/falcon_h1_check>/<seed>.json``."""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

SHARED_PAGES = 2
BODIES = (160, 3, 77, 300)    # the publisher's, the shortened hit's, the
                              # restored hit's, the unshared one's
# between the largest reading of the program as configured and the
# smallest of what has to be refused (my chip runs, PR 44, calls 7 and 8,
# four seeds; PERF.md section 2): a request's largest row difference
# 0.027-0.030 as configured | 0.31 and up for fp8 weights (0.36 for
# key_multiplier dropped, 0.45 for a hit from zero state, whole sigmas for
# the others); its mean over the positions 0.0229-0.0235 | 0.085 for
# int8's least request, 0.21 for a zero-state hit's, 0.26 for fp8's
ROW_TOL_SIGMA = 0.1
ROW_MEAN_TOL_SIGMA = 0.04
# the mixer alone in float32 (my chip runs, PR 44, calls 7 and 8, four
# seeds): the program reads 3.2e-5-4.8e-5 (4.4e-6 on the CPU), its state
# kept in bf16 1.95e-3 (3.8e-3 on the CPU), B and C of the other group
# 1.4-2.8, the chunk's state dropped 3.0-3.7
MIXER_TOL_SIGMA = 4e-4
MIXER_TOKENS = 40
CONTROLS = ("float8_e4m3fn", "int8")
WRONG = {
    "ssm_out_multiplier_dropped": {"ssm_out_multiplier": 1.0},
    "key_multiplier_dropped": {"key_multiplier": 1.0},
    "mlp_down_multiplier_dropped": None,          # filled from the config
    "norm_before_the_gate": {"mamba_norm_before_gate": True},
}
# outside the rows' limits in every seed
REFUSED = ("float8_e4m3fn",) + tuple(WRONG) + (
    "hits_from_zero_state", "b_and_c_of_the_other_group")
# and not ``correct`` by the cell's own comparison of the served tokens
# (hits from zero state are not listed: their rows read 0.45-0.55 sigma
# and 51-62 of 66 served tokens are still the reference's argmax, so the
# largest token's gap read 0.28, 0.29 and 0.07 against 0.1 on three
# seeds: the rows refuse them, the tokens may not)
NOT_CORRECT = ("b_and_c_of_the_other_group",)
# read and reported: served with the state kept in bf16 the rows read
# what the float32 state's read (0.0228-0.0235 both: a state rounded to
# bf16 at every step is as exact as the bf16 products around it, at
# these lengths); one float32 mixer tells them apart (``mixer_alone``)
REPORTED = ("bfloat16_state",)
MIXER_REFUSED = ("state_dropped_before_decode", "b_and_c_of_the_other_group",
                 "bfloat16_state")


def other_groups(params, sizes):
    """The weights of a model whose heads read B and C of the other
    group (of two): the groups' columns of the in-projection, and their
    taps and bias, change places."""
    import numpy as np
    d, n = sizes["mamba_d_ssm"], sizes["mamba_d_state"]
    assert sizes["mamba_n_groups"] == 2
    swap = np.arange(2 * d + 4 * n + sizes["mamba_n_heads"])
    for start in (2 * d, 2 * d + 2 * n):
        swap[start:start + 2 * n] = np.roll(swap[start:start + 2 * n], n)
    conv = swap[d:2 * d + 4 * n] - d
    out = dict(params)
    for i in range(sizes["num_hidden_layers"]):
        layer = dict(params[f"layers_{i}"])
        mixer = dict(layer["mixer"])
        mixer["in_proj"] = {"kernel": mixer["in_proj"]["kernel"][:, swap]}
        mixer["conv_w"] = mixer["conv_w"][:, conv]
        mixer["conv_b"] = mixer["conv_b"][conv]
        layer["mixer"] = mixer
        out[f"layers_{i}"] = layer
    return out


def mixer_alone(config, sizes, seed):
    """One mixer at the configuration's widths, float32 throughout (and
    once more with its state kept in bf16, the third control): a
    chunk of two pages from zero state and ``MIXER_TOKENS`` decode tokens
    (eight rows, rows 0, 3 and 5 decoding the same sequence, the others
    idle with a state that must not move) against the family's per-token
    recurrence over the whole sequence. Returns the largest difference
    over the decode positions in sigmas of the reference's, for the
    program as it is and for the controls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import families, reference
    from deepspeed_tpu.models.layers import Mamba2Mixer
    family = families.load(config)
    page = sizes["mamba_chunk_size"]
    build = lambda state_dtype: Mamba2Mixer(
        d_model=sizes["hidden_size"], d_ssm=sizes["mamba_d_ssm"],
        n_heads=sizes["mamba_n_heads"], d_head=sizes["mamba_d_head"],
        d_state=sizes["mamba_d_state"], n_groups=sizes["mamba_n_groups"],
        d_conv=sizes["mamba_d_conv"], chunk=page,
        in_multiplier=config["ssm_in_multiplier"],
        mup=tuple(config["ssm_multipliers"]),
        norm_epsilon=config["rms_norm_eps"], state_dtype=state_dtype,
        dtype=jnp.float32, param_dtype=jnp.float32)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    k_init, k_x = jax.random.split(key)
    total = 2 * page + MIXER_TOKENS
    x = jax.random.normal(k_x, (1, total, sizes["hidden_size"]))
    rows, live = 8, np.array([0, 3, 5])
    active = jnp.zeros((rows,), bool).at[live].set(True)

    def program(mixer):
        @jax.jit
        def run(x, drop):
            import flax.core.meta as meta
            variables = meta.unbox(mixer.init(k_init, x[:, :8]))
            zeros = {"conv_state": jnp.zeros((
                1, 3, sizes["mamba_d_ssm"] + 2 * sizes["mamba_n_groups"]
                * sizes["mamba_d_state"])),
                "ssm_state": jnp.zeros((1,) + family.state_shape(sizes),
                                       mixer.state_dtype)}
            _, out = mixer.apply({**variables, "cache": zeros},
                                 x[:, :2 * page], decode=True,
                                 mutable=["cache"])
            # eight slots: the chunk's state in the rows that decode, ones
            # in the idle rows
            cache = jax.tree.map(
                lambda a: jnp.where(
                    active.reshape((rows,) + (1,) * (a.ndim - 1)),
                    jnp.where(drop, 0.0, a), 1.0).astype(a.dtype),
                out["cache"])
            ys = []
            for t in range(2 * page, total):
                tok = jnp.broadcast_to(x[:, t:t + 1],
                                       (rows, 1, x.shape[-1]))
                y, out = mixer.apply({**variables, "cache": cache}, tok,
                                     decode=True,
                                     token_mask=active[:, None],
                                     mutable=["cache"])
                cache = out["cache"]
                ys.append(y[:, 0])
            idle = jnp.stack([jnp.abs(cache["ssm_state"][r] - 1.0).max()
                              for r in range(rows) if r not in live])
            return (jnp.stack(ys, 1) * config["ssm_out_multiplier"],
                    variables["params"], idle.max())
        return run

    def wanted(params, swap):
        if swap:
            params = other_groups({"layers_0": {"mixer": params}}, dict(
                sizes, num_hidden_layers=1))["layers_0"]["mixer"]
        return family._mixer(x, params, sizes, config)[0, 2 * page:]

    def gap(got, want):
        return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                     / np.asarray(want).std())

    with reference.highest():
        run = program(build(jnp.float32))
        got, params, idle = run(x, False)
        broken, _, _ = run(x, True)
        rounded, _, _ = program(build(jnp.bfloat16))(x, False)
        want, swapped = wanted(params, False), wanted(params, True)
    sound = max(gap(got[r], want) for r in live)
    return {"as_configured": sound,
            "state_dropped_before_decode": gap(broken[0], want),
            "b_and_c_of_the_other_group": gap(got[0], swapped),
            "bfloat16_state": gap(rounded[0], want),
            "idle_rows_state_moved": float(idle),
            "limit": MIXER_TOL_SIGMA,
            "within": sound <= MIXER_TOL_SIGMA and float(idle) == 0.0}


def serve(module, params, config, serving, seen, rounds, new_tokens,
          break_state=None):
    """``rounds`` of prompts, each served to its end before the next.
    ``break_state`` zeroes every snapshot before the round of that
    number. Returns ``[(prompt, handle, rows)]`` and the counters'
    reading after each round."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from benchmarks.chip.tools.lfm2_check import DispatchLog
    from deepspeed_tpu.inference import cache
    from deepspeed_tpu.observability.metrics import get_registry
    reg = get_registry()
    names = ("state_snapshots_restored", "state_resets",
             "state_restore_missed", "state_snapshots_taken",
             "state_snapshots_evicted")
    before = {n: reg.counter("serving/" + n).value for n in names}
    srv = ds.init_inference(
        module, params=params,
        dtype=getattr(jnp, config["compute_dtype"])).serve(dict(serving))
    logged = DispatchLog(srv, seen)
    served, counts = [], []
    for i, prompts in enumerate(rounds):
        if i == break_state:
            mgr = srv._paged
            mgr.pool = cache._walk_state(mgr.pool, lambda unit: dict(
                unit, snapshots=jax.tree.map(jnp.zeros_like,
                                             unit["snapshots"])))
        handles = [srv.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        srv.run()
        served += list(zip(prompts, handles))
        counts.append(dict(
            {n: reg.counter("serving/" + n).value - before[n]
             for n in names},
            tokens_reused=srv.metrics.prefill_tokens_reused))
    out = [(p, h, logged.rows(h)) for p, h in served]
    srv.close()
    del srv, logged
    gc.collect()
    return out, counts


def reading(requests):
    out = {"requests": requests,
           "row_max": max(r["max_diff_sigma"] for r in requests),
           "row_mean": max(r["mean_diff_sigma"] for r in requests),
           "row_mean_least": min(r["mean_diff_sigma"] for r in requests)}
    out["within"] = (out["row_max"] <= ROW_TOL_SIGMA
                     and out["row_mean"] <= ROW_MEAN_TOL_SIGMA)
    return out


def check_seed(args, config, seed, seen, watched):
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip import families, model, reference, serve_runner
    from benchmarks.chip.tools.lfm2_check import row_gaps
    from deepspeed_tpu.serving.paging import manager

    family = families.load(config)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    page = serving["paging"]["page_len"]
    slots = serving["num_slots"]
    module = family.build(config, args.rehearse)
    params = model.seeded_params(module, seed)
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(1, sizes["vocab_size"], size=n,
                                  dtype=np.int32)
    # the system prompt, short enough that the first request's own leaf
    # lies a page past it (a rehearsal's slots hold three pages)
    shared = min(SHARED_PAGES, (serving["max_len"] - args.new) // page - 1)
    system = draw(shared * page)
    room = serving["max_len"] - shared * page - args.new
    bodies = [min(n, room) for n in BODIES]
    first = np.concatenate([system, draw(bodies[0])])
    rounds = [[first], [np.concatenate([system, draw(bodies[1])])],
              [np.concatenate([system, draw(bodies[2])]), first,
               draw(bodies[3])][:slots]]
    kinds = ["cold", "hit_shortened", "hit_restored", "hit_at_the_leaf",
             "unshared"]
    # more leaves than the snapshot pool has entries, then the first
    # prompt again: every snapshot under its pages has been evicted
    entries = serving["paging"].get("state_snapshots", slots // 2)
    crowd = [draw(page + 2 + i % 5) for i in range(entries + 4)]
    crowded = [crowd[i:i + slots] for i in range(0, len(crowd), slots)]
    judged = len(rounds)                     # rounds whose rows are read

    def forward(wrong=None, lower=None):
        cfg = dict(config, **(wrong or {}))
        return jax.jit(lambda p, ids: family.reference_logits(
            p, ids, sizes, cfg, lower))

    def wanted(prompt, handle, fn, weights=params):
        ids = np.concatenate([prompt, handle.output_tokens])
        with reference.highest():
            return np.asarray(fn(weights, jnp.asarray(ids[None]))[0])[
                len(prompt) - 1:len(ids) - 1]

    def cells_own(served, weights=params):
        """``correct`` as a run of the cell would say it of these
        requests' served tokens."""
        check = serve_runner._reference_check(
            family, weights, [types.SimpleNamespace(
                spec={"prompt": p}, handle=h) for p, h, _ in served],
            sizes, config, serving["max_len"])
        return {"token_gap_max": check["max"], "tokens": check["tokens"],
                "the_references_argmax": check["exact"],
                "correct": bool(check["tokens"] > 0 and check["max"]
                                <= serve_runner.LOGIT_TOL_SIGMA)}

    def served(rounds, break_state=None, module=module):
        sample, manager._sample_impl = manager._sample_impl, watched
        try:
            return serve(module, params, config, serving, seen, rounds,
                         args.new, break_state)
        finally:
            manager._sample_impl = sample

    def rows_read(requests, fn, kinds=None, weights=params):
        return reading([
            dict(row_gaps(rows, wanted(p, h, fn, weights)),
                 prompt_len=len(p), **({"kind": kinds[i]} if kinds else {}))
            for i, (p, h, rows) in enumerate(requests)])

    out = {"seed": seed, "new_tokens": args.new,
           "limits": {"row_max": ROW_TOL_SIGMA,
                      "row_mean": ROW_MEAN_TOL_SIGMA,
                      "token_gap": serve_runner.LOGIT_TOL_SIGMA}}
    plain = forward()
    sound, counts = served(rounds + crowded + [[first]])
    evicted, sound = sound[-1:], sound[:len(kinds)]
    leaf = len(first) // page * page
    assert leaf > shared * page
    # the second was shortened to nothing; the third restored the
    # snapshot it took, the fourth the first one's own leaf
    expect = {"state_restore_missed": 1, "state_snapshots_restored": 2,
              "state_resets": 3, "state_snapshots_evicted": 0,
              "tokens_reused": shared * page + leaf}
    got = {k: counts[judged - 1][k] for k in expect}
    assert got == expect, (got, expect)
    # the crowd's leaves pushed every older snapshot out; at the cell's
    # size the first prompt's pages are still cached, so the third
    # request for it is a hit shortened to nothing (a rehearsal's 13
    # pages may have let them go: then it is cold, and as good a check)
    last = counts[-1]
    assert last["state_snapshots_evicted"] >= len(crowd) + 3 - entries, last
    assert last["state_snapshots_restored"] == 2, last
    if not args.rehearse:
        assert last["state_restore_missed"] == 2, last
        assert last["tokens_reused"] == expect["tokens_reused"], last
    out["counters"], out["counters_at_the_end"] = counts[judged - 1], last
    want = [wanted(p, h, plain) for p, h, _ in sound]
    out["as_configured"] = reading([
        dict(row_gaps(rows, w), prompt_len=len(p), kind=kind)
        for kind, (p, _, rows), w in zip(kinds, sound, want)])
    out["as_configured"]["the_cells_own"] = cells_own(sound)
    out["snapshots_evicted"] = rows_read(evicted, plain, ["evicted"])
    out["snapshots_evicted"]["the_cells_own"] = cells_own(evicted)
    hits = [kinds.index("hit_restored"), kinds.index("hit_at_the_leaf")]
    broken, _ = served(rounds, break_state=judged - 1)
    broken = [broken[i] for i in hits]
    out["hits_from_zero_state"] = rows_read(
        broken, plain, [kinds[i] for i in hits])
    out["hits_from_zero_state"]["the_cells_own"] = cells_own(broken)
    if config["ssm_state_dtype"] == "float32":
        rounded, _ = served(rounds, module=family.build(
            config, args.rehearse, ssm_state_dtype=jnp.bfloat16))
        out["bfloat16_state"] = rows_read(rounded, plain, kinds)
        out["bfloat16_state"]["the_cells_own"] = cells_own(rounded)
        del rounded
    wrong = dict(WRONG, mlp_down_multiplier_dropped={
        "mlp_multipliers": [config["mlp_multipliers"][0], 1.0]})
    for name, keys in wrong.items():
        out[name] = rows_read(sound, forward(keys))
    swapped = other_groups(params, sizes)
    out["b_and_c_of_the_other_group"] = rows_read(sound, plain,
                                                  weights=swapped)
    out["b_and_c_of_the_other_group"]["the_cells_own"] = cells_own(
        sound, swapped)
    del swapped

    def lowered(kind):
        # weight-only: the mixer's taps, biases and vectors and the norms
        # stay (vectors all: lower_weights leaves them; the taps are a
        # matrix by shape only)
        def lower(tree):
            low = reference.lower_weights(tree, kind)
            if "mixer" in tree:
                low["mixer"] = dict(low["mixer"],
                                    conv_w=tree["mixer"]["conv_w"])
            return low
        return lower

    for kind in CONTROLS:
        fn = forward(lower=lowered(kind))
        out[kind] = reading([
            dict(row_gaps(wanted(p, h, fn), w), prompt_len=len(p))
            for (p, h, _), w in zip(sound, want)])
    out["mixer_alone_float32"] = mixer_alone(config, sizes, seed)
    for name, arm in out.items():
        if isinstance(arm, dict) and "within" in arm:
            print(f"seed {seed} {name}: " + json.dumps(
                {f: arm[f] for f in arm if f != "requests"}), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="falcon-h1-34b-9l-serve",
                    help="a file of benchmarks/chip/configs, by name")
    ap.add_argument("--seeds", default="2147640044")
    ap.add_argument("--new", type=int, default=33,
                    help="tokens a request generates: one from its prefill "
                         "and --new - 1 decode steps")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "falcon_h1_check"))
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmarks.chip import manifest
    from deepspeed_tpu.serving.paging import manager
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("falcon_h1_check: not on a tpu (--rehearse runs the CPU "
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
        alone = out["mixer_alone_float32"]
        passed = [name for name in REFUSED + ("int8",)
                  if out[name]["within"]]
        passed += [name + " (by the cell's own comparison)"
                   for name in NOT_CORRECT
                   if out[name]["the_cells_own"]["correct"]]
        passed += ["mixer alone: " + name for name in MIXER_REFUSED
                   if alone[name] <= MIXER_TOL_SIGMA]
        sound = sound and alone["within"] and not passed and all(
            out[arm]["within"] and out[arm]["the_cells_own"]["correct"]
            for arm in ("as_configured", "snapshots_evicted"))
        print(f"seed {seed}: as configured "
              f"{'within' if out['as_configured']['within'] else 'OVER'} "
              f"the limits ({ROW_TOL_SIGMA} / {ROW_MEAN_TOL_SIGMA} sigma); "
              f"not refused: {passed or 'none'}; reported: " + ", ".join(
                  f"{name} {out[name]['row_max']:.4f} | "
                  f"{out[name]['row_mean']:.4f}" for name in REPORTED
                  if name in out), flush=True)
        gc.collect()
    # a rehearsal at 64 wide holds the tool together, not the limits
    return 0 if sound or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
