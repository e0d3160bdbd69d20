"""Find the knee of a serving configuration once, when a cell is defined:
the highest arrival rate the server sustains. One process builds the
server and offers the mix's open loop at each of a few fixed rates in
turn; the knee is read off the table (time to first token stops being
flat and the backlog at the window's end starts to grow) and written
into the traffic file as a number. A benchmark run never searches.

    chiprun -- python3 benchmarks/chip/tools/knee_sweep.py \
        --workload serve-1p3b-chat --rates 2 3 4 5 6 --seconds 25
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)


class _NoCompileLog:
    def mark(self):
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--lead-in", type=float, default=6.0)
    ap.add_argument("--drain", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args()
    args.rehearse, args.trace = False, 0

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    from benchmarks.chip import (families, manifest, model, serve_runner,
                                 traffic)
    from benchmarks.chip.stats import percentile
    configure_compile_cache()
    cell = manifest.Cell(ROOT, manifest.load(ROOT), args.workload)
    config = cell.config
    family = families.load(config)
    mix = traffic.resolve(cell.traffic, False)
    vocab = family.sizes(config, False)["vocab_size"]
    module = family.build(config, False)
    params = model.seeded_params(module, args.seed)
    srv = ds.init_inference(module, params=params,
                            dtype=getattr(jnp, config["compute_dtype"])
                            ).serve(dict(config["serving"]))
    for _ in serve_runner._warm(srv, vocab,
                                config["serving"]["paging"]["page_len"],
                                args.seed):
        pass
    print(f"{jax.devices()[0].device_kind}: server warm", flush=True)
    rows = []
    for i, rate in enumerate(args.rates):
        at = dict(mix, rate_per_s=rate)
        # fresh tokens at every rate: a replayed prompt would be served
        # from the prefix cache and flatter the time to first token
        sched = traffic.open_schedule(
            at, args.seed + i, vocab,
            args.lead_in + args.seconds + args.drain)
        hooks = serve_runner.Hooks(cell, args, _NoCompileLog(), args.seconds,
                                   1.0)
        t_lead = time.monotonic()
        out = serve_runner.drive_open(
            srv, sched, t_lead, args.lead_in, args.seconds, args.drain,
            hooks)
        judged, stopped = out["judged"], out["stopped"]
        w1 = out["window_start"] + args.seconds
        backlog = sum(1 for r in out["submitted"]
                      if r.submit is not None and r.submit < w1
                      and (r.first is None or r.first > w1))
        ttft = [1e3 * ((r.first or stopped) - r.due) for r in judged]
        late = ttft[len(ttft) // 2:]
        tpot = [1e3 * (r.last - r.first) / (r.tokens - 1) for r in judged
                if r.finished and r.tokens > 1]
        row = {"rate_per_s": rate, "judged": len(judged),
               "unfinished": sum(not r.finished for r in judged),
               "waiting_for_first_token_at_end": backlog,
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_p90_ms": percentile(ttft, 90),
               "ttft_p50_ms_second_half": percentile(late, 50),
               "tpot_p50_ms": percentile(tpot, 50),
               "tpot_p90_ms": percentile(tpot, 90),
               "iter_ms_p50": percentile(hooks.iter_ms, 50)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        srv.run()               # empty the server before the next rate
    out = os.path.join(ROOT, "chiprun_out", "knee")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".json"), "w") as f:
        json.dump(rows, f, indent=1)
    srv.close()


if __name__ == "__main__":
    main()
