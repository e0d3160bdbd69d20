"""Measure a cell the way the contract sets its bounds: sets of runs of
``run.py``, each run a new process with another ``--seed``, the same
seeds in every set; then for each metric the median and the spread
(interquartile distance as a share of the median) of each set. The
parent never touches JAX: a chip belongs to one process at a time.

    chiprun -- python3 benchmarks/chip/tools/sets.py --workload <name> \
        [--sets 2] [--runs 6] [--seconds N] [--traced 1] [--rehearse]

Every run's output goes to ``chiprun_out/sets/<workload>/``; the summary
is printed and written to ``summary.json`` there."""

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
from benchmarks.chip.stats import spread  # noqa: E402

BASE_SEED = 2147483659      # past 2**31, like the driver's


def one_run(workload, seed, seconds, trace, rehearse, log_path):
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks/chip/run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    wall = time.monotonic() - t0
    with open(log_path, "w") as f:
        f.write(proc.stdout)
    lines = proc.stdout.strip().splitlines()
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    phases = {}
    for ln in lines:
        m = re.search(r"set-up phases s: (\{.*?\}) \(of attach", ln)
        if m:
            phases = json.loads(m.group(1))
    return {"seed": seed, "rc": proc.returncode, "wall_s": wall,
            "result": result, "phases": phases}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs (--trace 1) after the sets")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    seeds = [BASE_SEED + 7919 * i for i in range(args.runs)]
    sets, failed = [], 0
    for s in range(args.sets):
        runs = []
        for seed in seeds:
            r = one_run(args.workload, seed, seconds, 0, args.rehearse,
                        os.path.join(out_dir, f"set{s}-seed{seed}.log"))
            failed += r["rc"] != 0
            m = (r["result"] or {}).get("metrics", {})
            print(f"set {s} seed {seed}: rc {r['rc']} wall "
                  f"{r['wall_s']:.1f}s correct "
                  f"{(r['result'] or {}).get('correct')} "
                  + " ".join(f"{k}={v['value']:.6g}" for k, v in m.items())
                  + " phases " + json.dumps(r["phases"]), flush=True)
            runs.append(r)
        sets.append(runs)
    traced = [one_run(args.workload, seeds[i % len(seeds)], seconds, 1,
                      args.rehearse,
                      os.path.join(out_dir, f"traced{i}.log"))
              for i in range(args.traced)]
    for r in traced:
        failed += r["rc"] != 0
        print("traced:", json.dumps(r["result"]), flush=True)

    summary = {"workload": args.workload, "seconds": seconds, "metrics": {},
               "phases": {}}
    names = sorted({k for runs in sets for r in runs
                    for k in (r["result"] or {}).get("metrics", {})})
    for name in names:
        per_set = []
        for runs in sets:
            vals = [r["result"]["metrics"][name]["value"] for r in runs
                    if r["result"] and name in r["result"]["metrics"]]
            # the first run of the first set compiles: its set-up is
            # recorded apart, as the driver does
            if name == "setup_s" and runs is sets[0]:
                first, vals = vals[0], vals[1:]
                summary["first_setup_s"] = first
            per_set.append({"median": statistics.median(vals),
                            "spread": spread(vals) if len(vals) > 1 else None,
                            "values": vals})
        summary["metrics"][name] = per_set
        print(name, " | ".join(
            f"median {p['median']:.6g} spread {p['spread']:.4g}"
            if p["spread"] is not None else f"median {p['median']:.6g}"
            for p in per_set))
    phase_names = sorted({k for runs in sets for r in runs
                          for k in r["phases"]})
    warm = [r for i, runs in enumerate(sets) for j, r in enumerate(runs)
            if (i, j) != (0, 0)]
    for name in phase_names:
        vals = [r["phases"][name] for r in warm if name in r["phases"]]
        if len(vals) > 1:
            q1, med, q3 = statistics.quantiles(vals, n=4)
            summary["phases"][name] = {"median": med, "iqr": q3 - q1,
                                       "min": min(vals), "max": max(vals)}
            print(f"phase {name}: median {med:.3f} iqr {q3 - q1:.3f} "
                  f"min {min(vals):.3f} max {max(vals):.3f} (n={len(vals)})")
    summary["traced"] = [r["result"] for r in traced]
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
