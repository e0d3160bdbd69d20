"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

Looks the cell up in the manifest, loads ``configs/<config>.json`` and
``traffic/<traffic>.json`` by name, picks the runner by the
configuration's ``kind`` (``train`` | ``serve``), warms the cell's own
shapes, measures for ``--seconds``, checks the outputs against the plain
float32 reference, and prints one JSON object as the last line of
standard output. ``--trace 0`` gives the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (one reader each, ``readers/``).
The line's last key, ``check``, holds each number that decided
``correct`` beside its limit (``{"value", "limit"}``; a count that has
no limit carries none), and the same go to standard error as the run's
last lines there: after a refusal they are what the ledger keeps.

``setup_s`` runs from the moment the chip is attached (``jax.devices()``
has returned) to the first instant of the measured window. The seconds
before that (``attach``: the interpreter, ``import jax``, libtpu taking
the chip) are the machine's, vary by seconds from run to run and can be
moved by no change to this repo (PERF.md has the measurements); they
are printed with the other phases on an earlier line of every run. On a platform other than ``tpu`` the run exits
nonzero unless ``--rehearse`` is given; a rehearsal runs a tiny stand-in
on the CPU and prints no metric at all.
"""

import time

_T0 = time.monotonic()      # the first statement: `attach` counts from here

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _say(msg):
    print(f"[bench +{time.monotonic() - _T0:7.2f}s] {msg}", flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the cell's tiny stand-in on the CPU; prints "
                         "the contract's last line with no metric in it")
    args = ap.parse_args(argv)
    args.out_dir = os.path.join(ROOT, ".bench_out")
    return args


def main(argv=None):
    args = _parse(argv)
    from benchmarks.chip import manifest, peaks, phases as phases_mod
    cell = manifest.Cell(ROOT, manifest.load(ROOT), args.workload)
    if args.rehearse:
        # the CPU stands in, with as many virtual devices as the cell
        # has chips; set before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = " ".join(
            [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
            + [f"--xla_force_host_platform_device_count={cell.chips}"])

    phases = phases_mod.Phases(_T0)
    import jax
    import_jax_s = time.monotonic() - _T0
    compile_log = phases_mod.CompileLog().install()     # before any jit
    devices = jax.devices()
    attached = phases.mark("attach")
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and not args.rehearse:
        print(f"run.py: JAX platform is {platform!r}, not 'tpu': a device "
              "metric comes only from the chip (--rehearse runs the tiny "
              "CPU stand-in)", file=sys.stderr)
        return 2
    if len(devices) != cell.chips:
        print(f"run.py: cell {cell.name!r} asks for {cell.chips} chip(s), "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    chip_peaks = None if args.rehearse else peaks.peaks_for(kind)

    import deepspeed_tpu  # noqa: F401
    from deepspeed_tpu.utils.host_env import configure_compile_cache
    cache_dir = configure_compile_cache()
    if args.rehearse:
        # XLA:CPU logs an error for every entry it reads back on another
        # machine type; a rehearsal has no set-up time to keep steady
        jax.config.update("jax_enable_compilation_cache", False)
    # JAX's defaults never write a program that compiled in under a
    # second, so it would compile again in every run: write them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    phases.mark("import")
    _say(f"cell {cell.name}: {len(devices)} x {kind} ({platform}), seed "
         f"{args.seed}, {args.seconds:g}s, trace {args.trace}, compile "
         f"cache {cache_dir}")

    from benchmarks.chip import serve_runner, train_runner
    runner = {"train": train_runner, "serve": serve_runner}[
        cell.config["kind"]]
    setup_mark = compile_log.mark()
    out = runner.run(cell, args, phases, compile_log, devices, _say)
    # compiles from the first jit to the start of the window: later ones
    # (the reference check's) are not set-up, and one inside the window
    # has already made the run incorrect
    setup = compile_log.since(
        setup_mark, out["observed"].pop("compile_mark_at_window"))
    setup_s = out["window_start"] - attached
    _say("set-up phases s: " + json.dumps(
        {k: round(v, 3) for k, v in phases.seconds.items()})
        + f" (of attach, {import_jax_s:.3f} to import jax)"
        + f"; setup_s {setup_s:.3f}; compile requests "
        f"{setup['requests_use_cache']}, cache hits {setup['cache_hits']}, "
        f"compiles {setup['compiles']}, backend_compile_duration "
        f"{setup['backend_compile_s']:.3f}s (read-back "
        f"{setup['retrieval_s']:.3f}s)")
    if setup["compiled"]:
        _say(f"compiled during set-up (not read back): {setup['compiled']}")

    peak = out["memory_peak_bytes"]
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    _say(f"memory stats of {devices[0]}: {devices[0].memory_stats()}")
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if args.rehearse:
        scratch = dict(line, metrics={}, device=dict(device))
        if args.trace:
            _per_layer(cell, out, setup, chip_peaks, peak, scratch,
                       rehearse=True)
        _say("rehearsal: control flow and counts only, no metric is "
             f"printed; readers gave a value for {sorted(scratch['metrics'])}")
    elif args.trace:
        _per_layer(cell, out, setup, chip_peaks, peak, line)
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end():
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    line["check"] = out["check"]        # last: what decided `correct`
    for failure in out.get("failures", ()):
        print(f"token over the limit: {json.dumps(failure)}",
              file=sys.stderr)
    for name, number in out["check"].items():
        print(f"check {name}: {json.dumps(number)}", file=sys.stderr)
    print(f"correct: {json.dumps(line['correct'])}", file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


def _per_layer(cell, out, setup, chip_peaks, peak, line, rehearse=False):
    """``--trace 1``: reduce the capture and hand every per-layer metric
    of the cell to its reader."""
    from benchmarks.chip import readers, xplane
    trace = None
    capture = out.get("capture")
    if capture is not None and capture.path:
        trace = xplane.load(capture.path)
        if rehearse:
            shutil.rmtree(capture.dir, ignore_errors=True)
        line["device"]["busy_s"] = xplane.busy_s(trace)
        line["device"]["window_s"] = trace.window_s
        line["breakdown"] = {"device_ops": xplane.top_ops(trace),
                             "idle_gaps": xplane.idle_gaps(trace)}
    observed = out["observed"]
    obs = readers.Observed(
        setup=setup, series=observed["series"], trace=trace,
        peaks=chip_peaks, facts=dict(observed, memory_peak_bytes=peak),
        say=_say)
    registry = readers.load_all()
    for m, spec in cell.per_layer():
        value = registry[spec["reader"]](obs, **spec.get("args", {}))
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}


if __name__ == "__main__":
    sys.exit(main())
