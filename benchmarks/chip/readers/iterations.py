"""The server's own log of its host loop: the table ``serving/iterations``
of the process registry (``deepspeed_tpu/serving/metrics.py``), one row an
``advance()`` that had work, nanoseconds on the host's clock. A row holds
``caller`` | ``empty`` (the previous such call's exit to this one's entry,
by whether requests were in flight then), the phases of the call itself
(``admit``, ``prefill_dispatch``, ``decode_dispatch``, ``readback``,
``harvest``, ``other``: they sum to exit - entry), ``gc`` (collector time
anywhere in the row, overlapping the phase it interrupted) and counts
(``rows_decoding``, ``chunk_pages``, ``compiles``, ``ready``, ``traced``).

Read are the rows before the first one that began under a tracer (the
profiler's start and stop stall the loop, as ``Hooks.host_cutoff`` says of
the runner's own series) in which nothing compiled: set-up's first calls
compile, and a compile is no stall. Warm-up and lead-in are in them, since
``facts`` does not say where the window lies: every number is over both,
and what is said of a single row (a stall, a largest) names warm-up's own
and the first row after it (a closed loop's callers all admitted in one
call), so that a run's count can be split by who reads it. Warm-up's rows
are known from the log itself: ``serve_runner._warm`` runs the server empty
twice, so they are the first two stretches of rows that open on ``empty``.
Three columns are made here: ``advance`` (the six phases), ``wall``
(``advance`` + ``caller`` + ``empty``) and ``host`` (``advance`` less a
``readback`` that waited for arrays not yet ready: what the host, not the
device, took). A program without the table (an older one) reads as
nothing."""

from . import reader
from ..stats import percentile

TABLE = "serving/iterations"
PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "readback",
          "harvest", "other")
# ``serve_runner._warm``: two ``srv.run()`` calls, each to an empty server
WARM_STRETCHES = 2
WARM, FIRST, RUN = ("warm-up's row", "the first row after warm-up",
                    "the row")


def rows_read(table):
    """The rows the metrics are made of, oldest first, as dicts with the
    three made columns and ``part`` (``WARM`` for warm-up's rows,
    ``FIRST`` for the first after them, else ``RUN``: only said, never
    counted apart); how many were set aside for having compiled; and the
    rows that began under the tracer, which only ``_rows`` speaks of. A
    ring that wrapped has lost warm-up's rows and marks none."""
    rows, traced, compiled = [], [], 0
    stretch = 0 if len(table) == table.count else WARM_STRETCHES + 1
    for cells in table.read():
        row = dict(zip(table.columns, cells))
        opens = bool(row["empty"])      # the server was empty before it
        stretch += opens
        row["part"] = (WARM if stretch <= WARM_STRETCHES else FIRST
                       if opens and stretch == WARM_STRETCHES + 1 else RUN)
        row["advance"] = sum(row[p] for p in PHASES)
        row["wall"] = row["advance"] + row["caller"] + row["empty"]
        row["host"] = row["advance"] - (0 if row["ready"]
                                        else row["readback"])
        if row["traced"]:
            traced.append(row)
        elif traced:
            break                       # the drain after the capture
        elif row["compiles"]:
            compiled += 1
        else:
            rows.append(row)
    return rows, compiled, traced


def _rows(obs):
    """Read once a run, and kept on ``obs`` for the cell's other metrics."""
    rows = getattr(obs, "iteration_rows", None)
    if rows is not None:
        return rows
    from deepspeed_tpu.observability.metrics import get_registry
    find = getattr(get_registry(), "table", None)
    table = find(TABLE) if find is not None else None
    rows = []
    if table is not None:
        rows, compiled, traced = rows_read(table)
        obs.say(f"{TABLE}: {len(rows)} rows read "
                f"({sum(r['part'] == WARM for r in rows)} of them warm-up's), "
                f"{compiled} that compiled set aside, of {len(table)} "
                f"retained and {table.count} written")
        wall = sum(r["wall"] for r in traced)
        if wall:
            # beside the device trace's own numbers, which are of these
            shares = ", ".join(
                f"{c} {100.0 * sum(r[c] for r in traced) / wall:.2f}%"
                for c in ("caller", "empty", "gc", "readback"))
            obs.say(f"{TABLE}: not read, the {len(traced)} rows that began "
                    f"under the tracer ({wall / 1e9:.3f}s): {shares}")
    obs.iteration_rows = rows
    return rows


def _selected(rows, where):
    return [r for r in rows if where is None or r[where] > 0]


def _describe(row, last):
    longest = max(PHASES, key=row.__getitem__)
    ms = {c: row[c] / 1e6 for c in PHASES + ("advance", "gc", "caller")}
    before = (last["t_entry"] - row["t_entry"]) / 1e9
    return (f"{row['part']} that entered {before:.3f}s before the last one "
            "read: "
            f"advance {ms['advance']:.3f} ms, most in {longest} "
            f"({ms[longest]:.3f} ms), readback {ms['readback']:.3f} ms "
            f"ready={row['ready']}, gc {ms['gc']:.3f} ms, caller "
            f"{ms['caller']:.3f} ms")


@reader("iterations_share_pct")
def iterations_share_pct(obs, num, den):
    """100 x the sum of the columns ``num`` / the sum of the columns
    ``den`` (lists of names), over the rows read."""
    rows = _rows(obs)
    below = sum(r[c] for r in rows for c in den)
    if not below:
        return None
    above = sum(r[c] for r in rows for c in num)
    obs.say(f"{' + '.join(num)} = {above / 1e9:.4f}s of {' + '.join(den)} "
            f"= {below / 1e9:.4f}s")
    return 100.0 * above / below


@reader("iterations_percentile_ms")
def iterations_percentile_ms(obs, column, q, where=None):
    """The ``q``-th percentile of ``column`` in ms, over the rows read
    whose ``where`` column is above 0."""
    values = [r[column] / 1e6 for r in _selected(_rows(obs), where)]
    if not values:
        return None
    return float(percentile(values, q))


@reader("iterations_max_ms")
def iterations_max_ms(obs, column, where=None):
    """The largest ``column`` in ms over the rows read whose ``where``
    column is above 0 (0 when there are rows and none is), and which row
    it was."""
    rows = _rows(obs)
    if not rows:
        return None
    chosen = _selected(rows, where)
    if not chosen:
        return 0.0
    worst = max(chosen, key=lambda r: r[column])
    obs.say(f"largest {column}: {_describe(worst, rows[-1])}")
    return worst[column] / 1e6


@reader("iterations_count")
def iterations_count(obs, column, over_ms):
    """How many of the rows read hold more than ``over_ms`` in
    ``column``, and which."""
    rows = _rows(obs)
    if not rows:
        return None
    over = [r for r in rows if r[column] > over_ms * 1e6]
    for r in over[:8]:
        obs.say(f"{column} over {over_ms} ms: {_describe(r, rows[-1])}")
    if over:
        obs.say(f"{column} over {over_ms} ms: {len(over)} rows, "
                f"{sum(r['part'] == WARM for r in over)} of them warm-up's, "
                f"{sum(r['part'] == FIRST for r in over)} the first after")
    return float(len(over))
