"""Compiled-program registry: one queryable table of every jit site.

Every jitted program the framework dispatches — the fused/parity train
steps, the pipelined tick, serving ``_admit``/``_decode_iter``, the
paged decode and chunk-prefill programs, the inference prefill/decode
loops — registers here through ``track_program(name, jax.jit(...))``.
The returned ``TrackedProgram`` is a transparent callable wrapper: it
forwards ``*args`` untouched (donation semantics included), counts
calls, and detects compile events by the jit cache growing across a
call (the same ``_cache_size()`` probe the compile-once tests already
assert on — those scattered assertions now have one shared table to
read). On a compile it records the wall time of that dispatch
(trace + XLA compile dominate it) and snapshots the ABSTRACT input tree
(shapes/dtypes only — device buffers are never retained, so tracking a
program never pins its operands).

Per-program HBM footprint and FLOPs come from
``compiled.memory_analysis()`` / ``cost_analysis()`` — pulled lazily by
``analyze()``, which re-lowers from the stored avals and compiles a
fresh executable. That is an explicitly expensive, off-the-step-path
operation (``ds_tpu_trace --memory``, ``ds_tpu_report``, tests); the
per-call tracking cost is two cache-size probes and two clock reads.

Stdlib-only at module level (the dependency-free tooling contract of
this package): jax is imported inside the functions that need it.
"""

import time
from typing import Any, Callable, Dict, Optional, Tuple

from .goodput import note_compile
from .memory import _fmt_bytes, _leaf_bytes as _leaf_nbytes
from .metrics import collective_tally, diff_collective_tally, get_registry


class ProgramRecord:
    """Host-side bookkeeping for one registered program."""

    __slots__ = ("name", "subsystem", "calls", "compiles", "compile_wall_s",
                 "last_compile_wall_s", "arg_leaves", "arg_bytes",
                 "collective_bytes", "collective_bytes_per_call",
                 "analysis", "analysis_error")

    def __init__(self, name: str, subsystem: Optional[str] = None):
        self.name = name
        self.subsystem = subsystem
        self.calls = 0
        self.compiles = 0
        self.compile_wall_s = 0.0
        self.last_compile_wall_s: Optional[float] = None
        self.arg_leaves = 0            # shaped leaves in the last-compiled
        self.arg_bytes = 0             # input tree, and their total bytes
        # collectives traced while this program compiled: {op:axis ->
        # payload bytes}; every later execution of the program moves the
        # same bytes, so the sum IS the static bytes-moved-per-call
        # estimate (ICI vs DCN attributable from the axis names before
        # hardware is reachable)
        self.collective_bytes: dict = {}
        self.collective_bytes_per_call = 0
        self.analysis: Optional[dict] = None
        self.analysis_error: Optional[str] = None

    def to_dict(self) -> dict:
        out = {
            "subsystem": self.subsystem,
            "calls": self.calls,
            "compiles": self.compiles,
            "compile_wall_s": self.compile_wall_s,
            "last_compile_wall_s": self.last_compile_wall_s,
            "arg_leaves": self.arg_leaves,
            "arg_bytes": self.arg_bytes,
        }
        if self.collective_bytes:
            out["collective_bytes"] = dict(self.collective_bytes)
            out["collective_bytes_per_call"] = self.collective_bytes_per_call
        if self.analysis is not None:
            out["analysis"] = dict(self.analysis)
        if self.analysis_error is not None:
            out["analysis_error"] = self.analysis_error
        return out


class TrackedProgram:
    """Transparent jit wrapper: pass-through call + compile telemetry.

    Attribute access falls through to the wrapped jit function, so
    ``.lower()``, ``._cache_size()``, ``.clear_cache()`` and friends
    keep working on the tracked handle.
    """

    __slots__ = ("_fn", "_size_fn", "record", "_last_avals",
                 "_comm_counter")

    def __init__(self, fn: Callable, record: ProgramRecord):
        self._fn = fn
        self._size_fn = getattr(fn, "_cache_size", None)
        self.record = record
        self._last_avals: Optional[Tuple[tuple, dict]] = None
        self._comm_counter = None      # set at compile when the program
                                       # traced any collectives

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return (f"TrackedProgram({self.record.name!r}, "
                f"compiles={self.record.compiles})")

    def __call__(self, *args, **kwargs):
        size_fn = self._size_fn
        if size_fn is None:               # not a jit wrapper: plain call
            self.record.calls += 1
            return self._fn(*args, **kwargs)
        before = size_fn()
        comm_before = collective_tally()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        rec = self.record
        rec.calls += 1
        if size_fn() > before:
            wall = time.perf_counter() - t0
            rec.compiles += 1
            rec.compile_wall_s += wall
            rec.last_compile_wall_s = wall
            self._snapshot_args(args, kwargs)
            reg = get_registry()
            reg.counter("programs/compiles_total").inc()
            reg.histogram("programs/compile_wall_s").observe(wall)
            # goodput: the containing timed("compute") site just paid
            # this wall as compute — re-attribute it to compile
            note_compile(wall)
            # collectives traced during THIS dispatch belong to this
            # program: the static per-call bytes-moved estimate
            traced = diff_collective_tally(comm_before)
            if traced:
                rec.collective_bytes = traced
                rec.collective_bytes_per_call = sum(traced.values())
                self._comm_counter = reg.counter("comm/program_bytes_total")
        if self._comm_counter is not None:
            # cumulative EXECUTED traffic: per-call estimate x calls —
            # one host int add per dispatch, no device work
            self._comm_counter.inc(rec.collective_bytes_per_call)
        return out

    def compile_ahead(self, *args, static_argnums=()):
        """Lower and compile the wrapped jit for ``args`` (arrays or
        ``ShapeDtypeStruct``s; nothing runs, nothing is donated) and
        return a callable that takes the same arguments and runs that
        executable: a shape's first dispatch then neither traces nor
        compiles, which a call through the jit would (``.lower()`` does
        not fill the jit's own cache). ``static_argnums`` are the
        wrapped jit's, which the executable leaves out of its call. The
        record counts the compile and the calls as it does the jit's."""
        t0 = time.perf_counter()
        compiled = self._fn.lower(*args).compile()
        wall = time.perf_counter() - t0
        rec = self.record
        rec.compiles += 1
        rec.compile_wall_s += wall
        rec.last_compile_wall_s = wall
        self._snapshot_args(args, {})
        reg = get_registry()
        reg.counter("programs/compiles_total").inc()
        reg.histogram("programs/compile_wall_s").observe(wall)
        dynamic = [i for i in range(len(args)) if i not in static_argnums]

        def run(*call_args):
            rec.calls += 1
            return compiled(*[call_args[i] for i in dynamic])

        return run

    def _snapshot_args(self, args, kwargs):
        """Keep the abstract input tree of the compile that just
        happened: shaped leaves become ShapeDtypeStructs (no buffer
        references survive), hashable statics pass through verbatim so
        ``analyze()`` can re-lower the exact specialization."""
        import jax

        def aval(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        avals = jax.tree.map(aval, (args, dict(kwargs)))
        rec = self.record
        rec.arg_leaves = sum(
            1 for leaf in jax.tree.leaves(avals) if hasattr(leaf, "shape"))
        rec.arg_bytes = sum(
            _leaf_nbytes(leaf) for leaf in jax.tree.leaves(avals))
        self._last_avals = avals

    def analyze(self) -> Optional[dict]:
        """Lower + compile from the stored avals and pull the XLA memory
        and cost analyses into the record. EXPENSIVE (a fresh XLA
        compile) — for ``ds_tpu_trace --memory`` / reports / tests,
        never the step path. Returns the analysis dict, or None when the
        program has not compiled yet or analysis is unavailable."""
        if self._last_avals is None:
            return None
        lower = getattr(self._fn, "lower", None)
        if lower is None:
            return None
        args, kwargs = self._last_avals
        try:
            compiled = lower(*args, **kwargs).compile()
        except (TypeError, ValueError, RuntimeError,
                NotImplementedError) as e:
            self.record.analysis_error = f"{type(e).__name__}: {e}"
            return None
        info: Dict[str, Any] = {}
        try:
            ma = compiled.memory_analysis()
        except (RuntimeError, NotImplementedError, AttributeError):
            ma = None
        if ma is not None:
            for field, attr in (
                    ("argument_bytes", "argument_size_in_bytes"),
                    ("output_bytes", "output_size_in_bytes"),
                    ("temp_bytes", "temp_size_in_bytes"),
                    ("alias_bytes", "alias_size_in_bytes"),
                    ("generated_code_bytes", "generated_code_size_in_bytes")):
                val = getattr(ma, attr, None)
                if val is not None:
                    info[field] = int(val)
        try:
            cost = compiled.cost_analysis() or {}
        except (RuntimeError, NotImplementedError, AttributeError):
            cost = {}
        if cost.get("flops") is not None:
            info["flops"] = float(cost["flops"])
        if cost.get("bytes accessed") is not None:
            info["bytes_accessed"] = float(cost["bytes accessed"])
        self.record.analysis = info or None
        return self.record.analysis


class ProgramRegistry:
    """Process-wide name -> TrackedProgram table. Re-registering a name
    replaces the entry (engines rebuild their closures per instance; the
    table reflects the live programs)."""

    def __init__(self):
        self._programs: Dict[str, TrackedProgram] = {}

    def track(self, name: str, fn: Callable,
              subsystem: Optional[str] = None) -> TrackedProgram:
        tracked = TrackedProgram(fn, ProgramRecord(name, subsystem))
        self._programs[name] = tracked
        return tracked

    def get(self, name: str) -> Optional[TrackedProgram]:
        return self._programs.get(name)

    def programs(self) -> Dict[str, TrackedProgram]:
        return dict(self._programs)

    def analyze_all(self) -> None:
        """Run the lazy XLA analysis for every program that has compiled
        (expensive — CLI/report path only)."""
        for tracked in self._programs.values():
            tracked.analyze()

    def table(self) -> Dict[str, dict]:
        """JSON-able view of every record, insertion-ordered."""
        return {name: t.record.to_dict()
                for name, t in self._programs.items()}

    def reset(self) -> None:
        self._programs.clear()


_DEFAULT_PROGRAMS: Optional[ProgramRegistry] = None


def get_program_registry() -> ProgramRegistry:
    """The process-wide shared program registry."""
    global _DEFAULT_PROGRAMS
    if _DEFAULT_PROGRAMS is None:
        _DEFAULT_PROGRAMS = ProgramRegistry()
    return _DEFAULT_PROGRAMS


def track_program(name: str, fn: Callable,
                  subsystem: Optional[str] = None) -> TrackedProgram:
    """Register ``fn`` (a jitted callable) under ``name`` in the shared
    registry and return the tracked wrapper to call in its place."""
    return get_program_registry().track(name, fn, subsystem=subsystem)


def format_program_table(table: Dict[str, dict]) -> str:
    """Render ``ProgramRegistry.table()`` as the text table
    ``ds_tpu_trace --memory`` / ``ds_tpu_report`` print."""
    if not table:
        return "(no compiled programs registered)"
    width = max(len("program"), max(len(n) for n in table))
    lines = [f"{'program':<{width}}  {'calls':>7}  {'compiles':>8}  "
             f"{'compile s':>9}  {'args':>9}  {'temp':>9}  {'flops':>10}"]
    for name, rec in table.items():
        analysis = rec.get("analysis") or {}
        flops = analysis.get("flops")
        flops_s = f"{flops / 1e9:.2f}G" if flops is not None else "-"
        lines.append(
            f"{name:<{width}}  {rec['calls']:>7}  {rec['compiles']:>8}  "
            f"{rec['compile_wall_s']:>9.3f}  "
            f"{_fmt_bytes(rec['arg_bytes']):>9}  "
            f"{_fmt_bytes(analysis.get('temp_bytes')):>9}  {flops_s:>10}")
    return "\n".join(lines)
