"""The traced part of a ``--trace 1`` run: a short capture by JAX's
profiler, with the program's own spans (``observability/trace.py``:
``fwd_bwd_step``, ``serving/decode_iter``, ...) and the benchmark's
``bench/*`` spans written into it as ``TraceAnnotation``s, so that host
and device share one clock. End-to-end numbers are taken with the
profiler off (``--trace 0``)."""

import contextlib
import glob
import os
import shutil
import time

WINDOW_SPAN = "bench/trace_window"


def annotate(name):
    """A host span of the benchmark's own, on the profiler's host lines.
    Costs a few hundred nanoseconds while no capture is running."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """Where a capture went: the ``.xplane.pb`` and the host-clock
    seconds it spanned."""
    path = None
    dir = None
    seconds = None


@contextlib.contextmanager
def capture(cell, args):
    import jax
    from deepspeed_tpu.observability import trace as spans

    # on the chip one run at a time holds a checkout, and its trace stays
    # for a look until the next run's takes its place; rehearsals run
    # several at once (a test file a worker), and two of one cell in one
    # directory delete or double each other's trace: a directory each,
    # removed once it is read (``run.py``)
    logdir = os.path.join(args.out_dir, "trace", cell.name + (
        f".{os.getpid()}" if args.rehearse else ""))
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no event per Python call
    options.enable_hlo_proto = False    # the programs' text is not read
    cap = Capture()
    spans.activate(spans.Tracer())      # span() -> TraceAnnotation
    jax.profiler.start_trace(logdir, profiler_options=options)
    t0 = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield cap
    finally:
        cap.seconds = time.monotonic() - t0
        jax.profiler.stop_trace()
        spans.deactivate()
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}: {found}")
    cap.path, cap.dir = found[0], logdir
