"""Record the small device trace the reduction's test reads
(``fixtures/tiny.xplane.pb``), on the chip: a two-layer GPT trained for
two steps (flash kernel, and the ZeRO-3 collectives where there are four
chips) and a few served requests (paged kernel), at sizes that keep the
trace to some hundreds of KB. Also prints the trace's planes and lines,
so that a change in the profiler's layout is seen when the fixture is
made again.

    chiprun -- python3 benchmarks/chip/tools/record_fixture.py

writes ``chiprun_out/fixture/tiny.xplane.pb`` and ``summary.txt``.
"""

import dataclasses
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)


def _serve(ds, module, params, jnp, np):
    """Six requests over four slots through the paged server."""
    eng = ds.init_inference(module, dtype=jnp.bfloat16, params=params)
    srv = eng.serve({"num_slots": 4, "max_len": 256,
                     "paging": {"page_len": 128, "kernel": "auto"}})
    rng = np.random.default_rng(1)
    handles = [srv.submit(rng.integers(1, 512, size=int(n), dtype=np.int32),
                          max_new_tokens=6)
               for n in (130, 40, 200, 17, 129, 64)]
    srv.run()
    srv.close()
    return sum(len(h.output_tokens) for h in handles)


def main():
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = len(jax.devices())
    print(f"attach {time.monotonic() - t0:.2f}s, {n} x "
          f"{jax.devices()[0].device_kind}", flush=True)
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT, GPTConfig
    from deepspeed_tpu.observability import trace as spans
    from benchmarks.chip import model as bench_model
    from benchmarks.chip.families import gpt2

    out = os.path.join(ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    cfg = GPTConfig(vocab_size=512, max_seq_len=256, d_model=128, n_layers=2,
                    n_heads=2, scan_layers=True, remat="full",
                    dtype=jnp.bfloat16, param_dtype=jnp.float32)
    seq, micro = 256, 4
    # the server first, once, so that its programs are compiled before
    # the capture (the jitted programs are module-level: a second server
    # of an equal module reuses them)
    smodule = GPT(dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                                      remat="none"))
    sparams = bench_model.seeded_params(smodule, 0)
    _serve(ds, smodule, sparams, jnp, np)
    rows = micro * n
    engine, _, _, _ = ds.initialize(
        model=GPT(cfg), loss_fn=gpt2.train_loss({"loss_chunk": 128}),
        rng=jax.random.PRNGKey(0),
        sample_batch={"input_ids": np.zeros((1, seq), np.int32)},
        config={"train_batch_size": rows,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3 if n > 1 else 1},
                "steps_per_print": 10_000,
                **({"mesh": {"data": 1, "fsdp": n}} if n > 1 else {})})
    rng = np.random.default_rng(0)

    def step():
        batch = {"input_ids": rng.integers(0, 512, (rows, seq + 1),
                                           dtype=np.int32)}
        with jax.profiler.TraceAnnotation("bench/train_batch"):
            loss = engine.train_batch(batch)
            jax.block_until_ready((loss, engine.params))

    step(), step()
    spans.activate(spans.Tracer())
    logdir = os.path.join(out, "trace")
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no event per Python call
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/trace_window"):
        step(), step()
        time.sleep(0.002)           # a gap no span of the program covers
        step()
        engine.destroy()
        served = _serve(ds, smodule, sparams, jnp, np)
    jax.profiler.stop_trace()
    spans.deactivate()

    pb = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    shutil.copy(pb, os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(logdir)
    lines = [f"{os.path.getsize(os.path.join(out, 'tiny.xplane.pb'))} "
             f"bytes, {n} x {jax.devices()[0].device_kind}, served "
             f"{served} tokens"]
    data = jax.profiler.ProfileData.from_file(
        os.path.join(out, "tiny.xplane.pb"))
    for plane in data.planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                stats = {k: (v if not isinstance(v, (bytes, str))
                             or len(v) < 80 else str(v)[:80])
                         for k, v in ev.stats}
                lines.append(f"    {ev.name!r} start={ev.start_ns} "
                             f"dur={ev.duration_ns} {stats}")
    with open(os.path.join(out, "summary.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[:40]))


if __name__ == "__main__":
    main()
