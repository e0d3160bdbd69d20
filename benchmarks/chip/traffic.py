"""The one general traffic generator. A traffic mix is a data file of
parameters under ``traffic/``; this reads it and makes the work from
``--seed``.

Every seed gets the *same set* of sizes and arrival gaps: lengths and
gaps are the distribution's quantiles at evenly spaced points, laid out
in blocks of ``block`` requests and permuted inside each block. So two
seeds do the same work, and any window of a few blocks holds the same
mix. Token values and the shared system prompts come from the seed
alone. The order comes from the seed too, unless the file gives an
``order_seed``: then sizes and arrivals are one fixed sequence, replayed
by every run with the seed's tokens. A mix whose judged metric is a tail
of an open loop needs that: at four fifths of the knee the queue's tail
moves by a fifth with the order of arrivals alone (PERF.md).

Kinds of file:

``{"kind": "steps", "seq": S, "micro_per_chip": M}``
    training: a fresh batch of ``rows x (S + 1)`` tokens for every step.
``{"kind": "requests", "loop": "open", "rate_per_s": R, "arrivals": ...}``
    independent users: requests fall due on the wall clock whether or
    not earlier ones have finished (``poisson`` gaps, or ``bursts`` of
    ``size`` requests every ``every_s`` seconds).
``{"kind": "requests", "loop": "closed", "clients": C}``
    ``C`` callers, each sending its next request when its last returns.

Lengths are ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
``{"dist": "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.
``shared_prefix: {"share", "count", "tokens"}`` opens that share of the
requests with one of ``count`` seeded system prompts of ``tokens``
tokens. A ``rehearse`` object overrides keys for the CPU rehearsal."""

import math
from statistics import NormalDist

import numpy as np


def resolve(traffic, rehearse):
    """The mix as run: with the ``rehearse`` overrides laid over it when
    the run is a CPU rehearsal."""
    out = {k: v for k, v in traffic.items() if k != "rehearse"}
    if rehearse:
        out.update(traffic.get("rehearse", {}))
    return out


def quantile(dist, u):
    """The distribution's value at cumulative share ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(dist["max"], max(dist["min"], round(x))))


def _block_points(block):
    return [(i + 0.5) / block for i in range(block)]


def train_batch(rng, rows, seq, vocab):
    """One step's tokens, made on the host: the input pipeline runs."""
    return {"input_ids": rng.integers(0, vocab, size=(rows, seq + 1),
                                      dtype=np.int32)}


class RequestStream:
    """An endless seeded stream of requests:
    ``{"id", "prompt", "max_new_tokens", "gap_s", "kind"}``. ``gap_s`` is
    the time since the previous request fell due (open loop)."""

    def __init__(self, traffic, seed, vocab):
        self.t = traffic
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.order = (np.random.default_rng(traffic["order_seed"])
                      if "order_seed" in traffic else self.rng)
        self.block = int(traffic.get("block", 32))
        sp = traffic.get("shared_prefix")
        self.prefixes = None
        if sp:
            self.prefixes = self.rng.integers(
                1, vocab, size=(sp["count"], sp["tokens"]), dtype=np.int32)
        self._queue = []
        self._next_id = 0

    def _gaps(self):
        arrivals = self.t.get("arrivals", {"process": "poisson"})
        if self.t.get("loop") != "open":
            return [0.0] * self.block
        if arrivals["process"] == "poisson":
            rate = self.t["rate_per_s"]
            return [-math.log(1.0 - u) / rate
                    for u in _block_points(self.block)]
        if arrivals["process"] == "bursts":
            size = arrivals["size"]
            return [arrivals["every_s"] if i % size == 0 else 0.0
                    for i in range(self.block)]
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")

    def _make_block(self):
        pts = _block_points(self.block)
        perm = self.order.permutation
        plens = [quantile(self.t["prompt_len"], u) for u in pts]
        olens = perm([quantile(self.t["output_len"], u) for u in pts])
        gaps = self._gaps()
        if self.t.get("arrivals", {}).get("process") != "bursts":
            gaps = perm(gaps)
        # which lengths open with a system prompt is fixed, evenly over
        # the distribution, so that every seed shares the same requests
        sp = self.t.get("shared_prefix")
        n_shared = int(round(sp["share"] * self.block)) if sp else 0
        every = self.block // n_shared if n_shared else 0
        shared = [bool(every) and i % every == every // 2
                  and i // every < n_shared for i in range(self.block)]
        which = 0
        for slot, i in enumerate(perm(self.block)):
            body = self.rng.integers(1, self.vocab, size=plens[i],
                                     dtype=np.int32)
            kind = "plain"
            if shared[i]:
                body = np.concatenate(
                    [self.prefixes[which % len(self.prefixes)], body])
                body = body[:self.t["prompt_len"]["max"]]
                which += 1
                kind = "shared_prefix"
            self._queue.append({
                "id": self._next_id, "prompt": body,
                "max_new_tokens": int(olens[slot]),
                "gap_s": float(gaps[slot]), "kind": kind})
            self._next_id += 1

    def take(self):
        if not self._queue:
            self._make_block()
        return self._queue.pop(0)


def open_schedule(traffic, seed, vocab, horizon_s):
    """Requests of an open loop with their due times (seconds from the
    start of the lead-in), up to ``horizon_s``."""
    stream = RequestStream(traffic, seed, vocab)
    out, due = [], 0.0
    while True:
        req = stream.take()
        due += req["gap_s"]
        if due > horizon_s:
            return out
        req["due_s"] = due
        out.append(req)
