"""A serving cell: ``ds.init_inference(...).serve(...)`` -> ``submit`` /
``advance``, driven from one thread. The load, the clocks and every
latency are the benchmark's own: a request is stamped when it falls due,
when it is submitted, and at each token through ``on_token``."""

import gc
import math
import time

import numpy as np

from . import families, model as bench_model
from . import reference, traffic as traffic_mod, tracing
from .phases import peak_bytes
from .stats import percentile, rate, samples_beyond, tail, token_spans_ms

# A served token may sit at most this many standard deviations of the
# reference's logits below the reference's best logit at its position
# (chip_smoke.py's rule). Never token equality: random weights have
# near-ties, and bf16 rounding flips 1-3% of them. The limit refuses a
# wrong path (a wrong attention path reads whole sigmas, PR 21) and
# nothing finer: the program's own int8 weight-only serving moves a
# logit only 1.8 times as far as bf16 does, and no number made from the
# served tokens alone parts the two by the factor of three a limit
# needs — the mean gap, the mean gap of the flipped tokens and the share
# of flipped tokens were read on twelve seeds of 3,750 tokens each
# (tools/control.py; both readings of each: PERF.md section 2). That
# takes the served logits, which the server does not hand out.
LOGIT_TOL_SIGMA = 0.1
CHECKED_REQUESTS = 4
# A run whose tokens fail says which (``_explain``, PR 54): no refusal
# of the nine on record before it left a seed, a request or a position
# behind (PERF.md section 2, "The lottery").
SAID_FAILURES = 8       # as many failing tokens go to standard error,
SAID_TOKENS = 64        # and as many to the run's log


class Record:
    """One request's clock stamps, all ``time.monotonic()``."""

    def __init__(self, spec, due, events=None):
        self.spec = spec
        self.due = due
        self.events = events        # [(time, record, is_first)], shared
        self.submit = None
        self.first = None
        self.last = None
        self.tokens = 0
        self.times = []             # every token's stamp
        self.handle = None

    def on_token(self, _request, _token):
        now = time.monotonic()
        if self.events is not None:
            self.events.append((now, self, self.first is None))
        if self.first is None:
            self.first = now
        self.last = now
        self.tokens += 1
        self.times.append(now)

    @property
    def finished(self):
        return self.handle is not None and self.handle.status == "finished"


def submit(srv, rec):
    rec.submit = time.monotonic()
    rec.handle = srv.submit(rec.spec["prompt"],
                            max_new_tokens=rec.spec["max_new_tokens"],
                            on_token=rec.on_token)


def drive_open(srv, schedule, t_lead, lead_in, seconds, drain, hooks):
    """Open loop: submit each request when it falls due, whatever the
    server is doing; advance the server in between. Requests due inside
    ``[window_start, window_end)`` are judged; the loop goes on (arrivals
    too) until each of them has finished or ``drain`` seconds have
    passed."""
    window_start = t_lead + lead_in
    window_end = window_start + seconds
    records = [Record(s, t_lead + s["due_s"]) for s in schedule]
    judged = [r for r in records if window_start <= r.due < window_end]
    nxt = 0
    while True:
        now = time.monotonic()
        while nxt < len(records) and records[nxt].due <= now:
            submit(srv, records[nxt])
            nxt += 1
        hooks(now, window_start, window_end)
        if now >= window_end and (
                all(r.handle is not None and r.handle.done for r in judged)
                or now >= window_end + drain):
            break
        if srv.busy:
            hooks.timed_advance(srv)
        elif nxt < len(records):
            time.sleep(max(0.0, min(records[nxt].due - now, 0.002)))
        else:
            time.sleep(0.002)
    finished = [r for r in judged if r.finished]
    return {
        "submitted": records, "judged": judged, "finished": finished,
        "failed": [r for r in judged if not r.finished],
        "tokens": sum(len(r.spec["prompt"]) + r.tokens for r in finished
                      if r.last < window_end),
        "window_start": window_start, "window_s": seconds, "stopped": now}


def drive_closed(srv, stream, clients, t_lead, lead_in, seconds, hooks):
    """Closed loop: ``clients`` callers, each sending its next request
    when its last one returns. Work is counted where it completes: a
    prompt's tokens when its first token arrives (its prefill is done),
    a generated token when it arrives. The window opens at the first
    first-token after the lead-in and closes at the first one ``seconds``
    later: prefill is served one request at a time, so between two such
    instants no prompt is half counted. Judged are the requests whose
    first token fell in the window."""
    events = []
    live = [Record(stream.take(), time.monotonic(), events)
            for _ in range(clients)]
    for rec in live:
        submit(srv, rec)
    finished, seen = [], 0
    window_start = window_end = None
    while window_end is None:
        now = time.monotonic()
        hooks(now, window_start if window_start is not None else math.inf,
              math.inf)
        hooks.timed_advance(srv)
        for t, _, is_first in events[seen:]:
            if not is_first:
                continue
            if window_start is None:
                if t >= t_lead + lead_in:
                    window_start = t
            elif window_end is None and t >= window_start + seconds:
                window_end = t
        seen = len(events)
        for i, rec in enumerate(live):
            if rec.handle.done:
                finished.append(rec)
                live[i] = Record(stream.take(), time.monotonic(), events)
                submit(srv, live[i])
    inside = [(t, rec, first) for t, rec, first in events
              if window_start < t <= window_end]
    judged = [rec for _, rec, first in inside if first]
    return {
        "submitted": [], "judged": judged, "finished": finished,
        "failed": [r for r in finished if not r.finished],
        "tokens": len(inside) + sum(len(r.spec["prompt"]) for r in judged),
        "window_start": window_start, "window_s": window_end - window_start,
        "stopped": window_end}


class Hooks:
    """What the loop does besides driving: notes when the window opens
    (compiles are counted from there), times each ``advance()``, and in
    a traced run captures ``trace_seconds`` at the end of the window."""

    def __init__(self, cell, args, compile_log, seconds, trace_seconds):
        self.cell, self.args = cell, args
        self.compile_log = compile_log
        self.seconds, self.trace_seconds = seconds, trace_seconds
        self.in_window = None
        self.iter_ms = []
        self.capture = None
        self._ctx = None
        self._capture_from = None
        self._window = (math.inf, math.inf)

    def __call__(self, now, window_start, window_end):
        self._window = (window_start, window_end)
        if self.in_window is None and now >= window_start:
            self.in_window = self.compile_log.mark()
        if not self.args.trace or now < window_start:
            return
        if self._capture_from is None:
            self._capture_from = (window_start + self.seconds
                                  - self.trace_seconds)
        if self._ctx is None and self.capture is None \
                and now >= self._capture_from:
            self._ctx = tracing.capture(self.cell, self.args)
            self.capture = self._ctx.__enter__()
            self._capture_until = now + self.trace_seconds
        elif self._ctx is not None and now >= self._capture_until:
            self.stop_capture()

    def stop_capture(self):
        if self._ctx is not None:
            ctx, self._ctx = self._ctx, None
            ctx.__exit__(None, None, None)

    @property
    def host_cutoff(self):
        """Host-clock samples count up to here: the profiler's start and
        stop stall the loop, so a traced run's host series end where its
        capture begins."""
        return self._capture_from if self.args.trace else math.inf

    def timed_advance(self, srv):
        t0 = time.monotonic()
        with tracing.annotate("bench/advance"):
            srv.advance()
        t1 = time.monotonic()
        if self._window[0] <= t0 and t1 < min(self._window[1],
                                              self.host_cutoff):
            self.iter_ms.append(1e3 * (t1 - t0))


def _warm(srv, vocab, page_len, seed):
    """One prefill chunk of each width the mix can hit (with the default
    ``prefill_chunk`` there is one: a page), one decode iteration, a
    prefix-cache hit, and a slot released — a fixed set of calls."""
    rng = np.random.default_rng(seed + 2)
    first = rng.integers(1, vocab, size=page_len + 2, dtype=np.int32)
    srv.submit(first, max_new_tokens=3)
    srv.run()
    yield
    again = np.concatenate([first[:page_len],
                            rng.integers(1, vocab, size=5, dtype=np.int32)])
    srv.submit(again, max_new_tokens=2)
    srv.submit(rng.integers(1, vocab, size=9, dtype=np.int32),
               max_new_tokens=2)
    srv.run()
    yield


def run(cell, args, phases, compile_log, devices, say):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.pallas import tuning

    config = cell.config
    family = families.load(config)
    mix = traffic_mod.resolve(cell.traffic, args.rehearse)
    sizes = family.sizes(config, args.rehearse)
    serving = (config["rehearse"]["serving"] if args.rehearse
               else config["serving"])
    vocab = sizes["vocab_size"]
    page_len = serving["paging"]["page_len"]

    tuning.clear_last_dispatch()
    module = family.build(config, args.rehearse)
    params = bench_model.seeded_params(module, args.seed)
    eng = ds.init_inference(module, params=params,
                            dtype=getattr(jnp, config["compute_dtype"]))
    srv = eng.serve(dict(serving))
    phases.mark("build")
    warm = _warm(srv, vocab, page_len, args.seed)
    next(warm)
    phases.mark("first_call")
    next(warm)
    if not args.rehearse:
        families.check_kernels(tuning.last_dispatch,
                               family.expected_kernels(serving))
    t_lead = phases.mark("warmup")

    lead_in, seconds = mix["lead_in_s"], args.seconds
    hooks = Hooks(cell, args, compile_log, seconds,
                  min(mix.get("trace_seconds", 2.0), seconds / 2))
    if mix["loop"] == "open":
        schedule = traffic_mod.open_schedule(
            mix, args.seed, vocab, lead_in + seconds + mix["drain_s"])
        out = drive_open(srv, schedule, t_lead, lead_in, seconds,
                         mix["drain_s"], hooks)
    else:
        out = drive_closed(
            srv, traffic_mod.RequestStream(mix, args.seed, vocab),
            mix["clients"], t_lead, lead_in, seconds, hooks)
    hooks.stop_capture()
    judged, window_start = out["judged"], out["window_start"]
    phases.seconds["lead_in"] = window_start - t_lead
    compiled = compile_log.since(hooks.in_window)
    peak = peak_bytes(devices)
    say(f"samples: {len(judged)} requests judged ({len(out['failed'])} not "
        f"finished), {samples_beyond(len(judged), 90)} beyond p90; "
        f"{len(hooks.iter_ms)} iterations timed; {out['tokens']} tokens "
        f"completed inside the {out['window_s']:.3f}s window")

    srv.close()
    sampled = _sample(out["finished"], args.seed)
    del srv
    gc.collect()                    # the page pool goes before the check
    verdict = decide(family, params, sizes, config, serving["max_len"], mix,
                     sampled, args.seed, say)
    if compiled["compile_events"]:
        say(f"COMPILED INSIDE THE WINDOW: {compiled['compiled']}")
    verdict["numbers"]["compiles_in_window"] = {
        "value": compiled["compile_events"], "limit": 0}
    correct = verdict["held"] and compiled["compile_events"] == 0

    # host-clock samples of a traced run end where its capture begins
    cutoff = hooks.host_cutoff
    end_to_end = {"serve_tokens_per_s": rate(out["tokens"], out["window_s"])}
    series = {}
    if mix["loop"] == "open":
        tails, series = _open_loop_tails(
            judged, out["stopped"], mix.get("itl_span_ms", 250.0), cutoff,
            say)
        end_to_end.update(tails)
    return {
        "correct": bool(correct), "attempted": len(judged),
        "failed": len(out["failed"]), "window_start": window_start,
        "memory_peak_bytes": peak, "check": verdict["numbers"],
        "failures": verdict["failures"][:SAID_FAILURES],
        "end_to_end": end_to_end,
        "observed": {
            "series": {
                "serve_iter_ms": hooks.iter_ms,
                "serve_gen_lag_ms": [
                    1e3 * (r.submit - r.due) for r in out["submitted"]
                    if r.submit is not None
                    and window_start <= r.due < cutoff],
                "serve_ttft_from_due_ms": [
                    1e3 * (r.first - r.due) for r in judged
                    if r.first is not None and r.first < cutoff],
                "serve_ttft_from_submit_ms": [
                    1e3 * (r.first - r.submit) for r in judged
                    if r.first is not None and r.first < cutoff],
                **series},
            "sizes": sizes, "chips": len(devices),
            "compiles_in_window": compiled["compile_events"],
            "compile_mark_at_window": hooks.in_window,
        },
        "capture": hooks.capture,
    }


def _open_loop_tails(judged, stopped, span_ms, cutoff, say):
    """Over every judged request: time to first token from the *due*
    time; the gap between tokens as one mean per request (``tpot``); and
    the gap between tokens over every stretch of a request's tokens that
    spans ``span_ms`` on the host's clock (``itl``: every gap of every
    request is in exactly one stretch). A request that never got there
    counts with the time it had waited when the run stopped. A stall of
    the server lands in one stretch of each request in flight, but in
    the mean of every one of them: with some 30 of 150 requests in
    flight the per-request tail *is* the stall (PERF.md)."""
    ttft = [1e3 * ((r.first if r.first is not None else stopped) - r.due)
            for r in judged]
    tpot = [1e3 * (r.last - r.first) / (r.tokens - 1)
            if r.finished and r.tokens > 1 else 1e3 * (stopped - r.due)
            for r in judged]
    itl = []
    for r in judged:
        itl.extend(token_spans_ms(r.times, span_ms))
        if not r.finished:
            itl.append(1e3 * (stopped - (r.last if r.last is not None
                                         else r.due)))
    gaps = [1e3 * (b - a) for r in judged
            for a, b in zip(r.times, r.times[1:])]
    tails = {"ttft_p90_ms": tail(ttft, 90), "tpot_p90_ms": tail(tpot, 90),
             "itl_p90_ms": tail(itl, 90)}
    say(f"p90 of all judged requests: ttft {tails['ttft_p90_ms']:.3f} ms, "
        f"tpot {tails['tpot_p90_ms']:.3f} ms, itl {tails['itl_p90_ms']:.3f}"
        f" ms over {len(itl)} stretches of {len(gaps)} gaps (largest gap "
        f"{max(gaps, default=0.0):.1f} ms); by nearest rank: ttft "
        f"{percentile(ttft, 90):.3f}, tpot {percentile(tpot, 90):.3f}, itl "
        f"{percentile(itl, 90):.3f}; sorted ttft ms "
        f"{[round(x, 1) for x in sorted(ttft)]}")
    done = [r for r in judged if r.finished and r.last < cutoff]
    series = {
        "serve_tpot_ms": [1e3 * (r.last - r.first) / (r.tokens - 1)
                          for r in done if r.tokens > 1]}
    return tails, series


def _sample(finished, seed):
    done = [r for r in finished if r.finished]
    pick = np.random.default_rng(seed + 3).permutation(len(done))
    return [done[i] for i in pick[:CHECKED_REQUESTS]]


def _reference_check(family, params, sampled, sizes, config, width):
    """Teacher-force each sampled request through the plain float32
    reference of the same weights, one request at a time at a fixed
    width, and reduce on the device: each position's gap between the
    best logit and the logit of the token that follows, and the standard
    deviation of the request's logits. Returns the largest and the mean
    gap of the served tokens in sigmas, how many are the reference's
    argmax, and every token's gap (``gaps``, for ``tools/control.py``)."""
    import jax
    import jax.numpy as jnp

    def reduce(p, ids, n):
        lg = family.reference_logits(p, ids, sizes, config)[0]
        follows = jnp.roll(ids[0], -1)
        gap = lg.max(-1) - jnp.take_along_axis(lg, follows[:, None], -1)[:, 0]
        real = (jnp.arange(lg.shape[0]) < n)[:, None]
        mean = jnp.sum(jnp.where(real, lg, 0.0)) / (n * lg.shape[1])
        var = jnp.sum(jnp.where(real, jnp.square(lg - mean), 0.0))
        return gap, jnp.sqrt(var / (n * lg.shape[1]))

    gaps = []
    with reference.highest():
        forward = jax.jit(reduce)
        for rec in sampled:
            prompt = np.asarray(rec.spec["prompt"])
            out = np.asarray(rec.handle.output_tokens, np.int32)
            n = len(prompt) + len(out)
            ids = np.zeros((1, width), np.int32)
            ids[0, :n] = np.concatenate([prompt, out])
            gap, sigma = forward(params, jnp.asarray(ids), n)
            gaps.append(np.asarray(gap)[len(prompt) - 1:n - 1]
                        / float(sigma))
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max": float(flat.max()) if flat.size else math.inf,
            "mean": float(flat.mean()) if flat.size else math.inf,
            "exact": int((flat == 0.0).sum()), "tokens": int(flat.size),
            "gaps": gaps}


def decide(family, params, sizes, config, width, mix, sampled, seed, say):
    """``correct`` of the served tokens: every one of the sampled
    requests within ``LOGIT_TOL_SIGMA`` of its position's best reference
    logit. Returns ``{"held", "numbers", "tokens", "failures"}``: whether
    they are, each number that decided beside its limit (the last line's
    ``check``), the reference check's reading, and one entry a token over
    the limit (``_explain``)."""
    t0 = time.monotonic()
    check = _reference_check(family, params, sampled, sizes, config, width)
    tol = LOGIT_TOL_SIGMA
    say(f"reference check: {check['exact']}/{check['tokens']} served tokens "
        f"of {len(sampled)} requests are the float32 argmax; largest logit "
        f"gap {check['max']:.4f} sigma (tolerance {tol} sigma), "
        f"mean {check['mean']:.2e}, in {time.monotonic() - t0:.1f}s")
    over = [(i, int(j)) for i, gaps in enumerate(check["gaps"])
            for j in np.flatnonzero(gaps > tol)]
    numbers = {"tokens_checked": {"value": check["tokens"]},
               "logit_gap_sigma": {     # no token served: no number
                   "value": check["max"] if check["tokens"] else None,
                   "limit": tol},
               "tokens_over": {"value": len(over), "limit": 0}}
    out = {"held": check["tokens"] > 0 and not over, "numbers": numbers,
           "tokens": check, "failures": []}
    if over:
        out["failures"] = _explain(family, params, sizes, config, width,
                                   sampled, check["gaps"], over, mix, seed,
                                   say)
        worst = max(out["failures"], key=lambda f: f["gap_sigma"])
        for name in ("request", "position", "near_tie_back", "choice_gap"):
            if worst[name] is not None:
                numbers["worst_token_" + name] = {"value": worst[name]}
    return out


def _spelled(numbers):
    return ", ".join(f"{k} {v:.4g}" if isinstance(v, float) else f"{k} {v}"
                     for k, v in numbers.items())


def _shared_tokens(spec, mix):
    """How many tokens a request opens with that others open with too."""
    if spec.get("kind") != "shared_prefix":
        return 0
    return min(mix["shared_prefix"]["tokens"], len(spec["prompt"]))


def _choice_gaps(family, params, sizes, config, width):
    """``fn(ids [n]) -> gap [n]``: the closest choice any router of the
    float32 reference made at each position of a request (one more pass
    of the reference, ``near_ties="gaps"``), or None for a family whose
    reference leaves no near-tie unjudged."""
    if not hasattr(family, "NEAR_TIE"):
        return None
    import jax
    import jax.numpy as jnp
    forward = jax.jit(lambda p, ids: family.reference_logits(
        p, ids, sizes, config, near_ties="gaps")[1][0])

    def gaps(ids):
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(ids)] = ids
        with reference.highest():
            return np.asarray(forward(params, jnp.asarray(padded)))[:len(ids)]

    return gaps


def _explain(family, params, sizes, config, width, sampled, gaps, over, mix,
             seed, say):
    """One entry a failing token, what a later session needs to look at
    it: the seed, the request's index in the mix's sequence, its prompt
    and shared prefix, the served token's position and its gap; and for
    a family whose routers can tie, how close the reference's closest
    choice was at the position that predicted it, how many positions
    back the reference's nearest own near-tie lies, and how many the
    request holds."""
    said, choice = [], {}
    choice_gaps = _choice_gaps(family, params, sizes, config, width)
    for i, j in over:
        rec = sampled[i]
        prompt = np.asarray(rec.spec["prompt"])
        if choice_gaps is not None and i not in choice:
            choice[i] = choice_gaps(np.concatenate(
                [prompt, np.asarray(rec.handle.output_tokens, np.int32)]))
        row = len(prompt) + j - 1       # the position that predicted it
        near = np.flatnonzero(choice[i] < family.NEAR_TIE) \
            if i in choice else None
        before = near[near <= row] if near is not None else ()
        one = {"seed": seed, "request": rec.spec.get("id"),
               "prompt_len": len(prompt),
               "shared_prefix": _shared_tokens(rec.spec, mix),
               "position": row + 1, "gap_sigma": float(gaps[i][j]),
               "choice_gap": float(choice[i][row]) if i in choice else None,
               "near_tie_back": int(row - before[-1]) if len(before)
               else None,
               "near_ties_in_request": len(near) if near is not None
               else None}
        said.append(one)
        if len(said) <= SAID_TOKENS:
            say("token over the limit: " + _spelled(one))
    if len(said) > SAID_TOKENS:
        say(f"and {len(said) - SAID_TOKENS} more tokens over the limit")
    return said
