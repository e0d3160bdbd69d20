"""The open loop times a request from when it fell due, not from when
it was submitted: a server that stalls shows the wait it imposed."""

import argparse
import time

import jax  # noqa: F401  (the loop's spans are TraceAnnotations: the
#                          import must not land in the first advance)
import numpy as np

from benchmarks.chip import serve_runner


class FakeRequest:
    def __init__(self, on_token, n):
        self.on_token, self.left = on_token, n
        self.status, self.done = "running", False
        self.output_tokens = []


class StallingServer:
    """Emits one token per live request per ``advance()``; the first
    ``advance()`` blocks for ``stall`` seconds."""

    def __init__(self, stall):
        self.stall, self.live = stall, []

    def submit(self, prompt, max_new_tokens, on_token):
        req = FakeRequest(on_token, max_new_tokens)
        self.live.append(req)
        return req

    @property
    def busy(self):
        return bool(self.live)

    def advance(self):
        if self.stall:
            time.sleep(self.stall)
            self.stall = 0.0
        time.sleep(0.001)
        for req in list(self.live):
            req.output_tokens.append(1)
            req.on_token(req, 1)
            req.left -= 1
            if req.left == 0:
                req.status, req.done = "finished", True
                self.live.remove(req)


class NoCompiles:
    def mark(self):
        return None


def _drive(stall):
    prompt = np.ones(4, np.int32)
    schedule = [{"prompt": prompt, "max_new_tokens": 3, "due_s": due}
                for due in (0.00, 0.05, 0.10, 0.15, 0.40)]
    hooks = serve_runner.Hooks(None, argparse.Namespace(trace=0),
                               NoCompiles(), 0.5, 0.1)
    t_lead = time.monotonic()
    out = serve_runner.drive_open(
        StallingServer(stall), schedule, t_lead, 0.0, 0.5, 2.0, hooks)
    return out["submitted"], out["judged"], hooks


def test_latency_counts_from_the_due_time_through_a_stall():
    records, judged, hooks = _drive(stall=0.3)
    assert len(judged) == 5 and all(r.finished for r in judged)
    # requests that fell due during the stall were submitted late ...
    lag = [r.submit - r.due for r in records]
    assert lag[1] > 0.2 and lag[2] > 0.15 and lag[3] > 0.1
    # ... and their time to first token holds that wait
    ttft = [r.first - r.due for r in records]
    assert ttft[1] > 0.2 and ttft[1] > (r_first_from_submit(records[1]) + 0.2)
    # the one that fell due after the stall did not wait
    assert ttft[4] < 0.05 and lag[4] < 0.02


def r_first_from_submit(rec):
    return rec.first - rec.submit


def test_without_a_stall_nothing_waits():
    records, judged, hooks = _drive(stall=0.0)
    assert max(r.first - r.due for r in records) < 0.05
    assert hooks.iter_ms and hooks.in_window is None


def test_a_request_that_never_finishes_is_counted_and_the_loop_ends():
    schedule = [{"prompt": np.ones(4, np.int32), "max_new_tokens": 10 ** 6,
                 "due_s": 0.0}]
    hooks = serve_runner.Hooks(None, argparse.Namespace(trace=0),
                               NoCompiles(), 0.1, 0.05)
    t0 = time.monotonic()
    out = serve_runner.drive_open(
        StallingServer(0.0), schedule, t0, 0.0, 0.1, 0.2, hooks)
    assert 0.3 <= out["stopped"] - t0 < 1.0
    assert out["failed"] == out["judged"] and len(out["judged"]) == 1
    assert out["finished"] == [] and out["tokens"] == 0
