"""The one traffic generator: bit-reproducible from the seed, another
order for another seed, and the same set of sizes for every seed."""

import json
import os

import numpy as np
import pytest

from benchmarks.chip import traffic

from ._paths import BENCH

def _request_mixes():
    """Every traffic file of requests: a mix is held by being there."""
    out = []
    for f in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        with open(os.path.join(BENCH, "traffic", f)) as fh:
            if json.load(fh)["kind"] == "requests":
                out.append(f[:-len(".json")])
    return out


MIXES = _request_mixes()
BIG = 2 ** 31 + 12345


def _mix(name, rehearse=False):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return traffic.resolve(json.load(f), rehearse)


def _take(mix, seed, n):
    stream = traffic.RequestStream(mix, seed, 50257)
    return [stream.take() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_the_same_seed_gives_the_same_requests_bit_for_bit(name):
    a, b = _take(_mix(name), BIG, 70), _take(_mix(name), BIG, 70)
    for x, y in zip(a, b):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert (x["max_new_tokens"], x["gap_s"], x["kind"]) == (
            y["max_new_tokens"], y["gap_s"], y["kind"])


@pytest.mark.parametrize("name", MIXES)
def test_another_seed_gives_the_same_sizes_in_another_order(name):
    mix = _mix(name)
    n = 2 * mix["block"]
    a, b = _take(mix, 1, n), _take(mix, BIG, n)
    same_order = [len(r["prompt"]) for r in a] == [len(r["prompt"])
                                                   for r in b]
    # a mix with an `order_seed` replays one fixed sequence of sizes
    assert same_order == ("order_seed" in mix)
    assert not np.array_equal(a[0]["prompt"][:8], b[0]["prompt"][:8])
    plain = lambda rs: sorted(len(r["prompt"]) for r in rs
                              if r["kind"] == "plain")
    assert plain(a) == plain(b)
    assert sorted(r["max_new_tokens"] for r in a) == \
        sorted(r["max_new_tokens"] for r in b)
    assert sorted(r["gap_s"] for r in a) == pytest.approx(
        sorted(r["gap_s"] for r in b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_mix_and_the_server(name):
    mix = _mix(name)
    for r in _take(mix, 7, 3 * mix["block"]):
        assert mix["prompt_len"]["min"] <= len(r["prompt"]) \
            <= mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= r["max_new_tokens"] \
            <= mix["output_len"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= 2048
        assert r["prompt"].dtype == np.int32 and r["prompt"].min() >= 1


def test_the_two_mixes_of_today_are_among_them():
    assert {"chat-open-0p8knee", "longprompt-closed-16"} <= set(MIXES)


def test_chat_shares_its_system_prompts_as_the_file_says():
    mix = _mix("chat-open-0p8knee")
    reqs = _take(mix, 3, mix["block"])
    shared = [r for r in reqs if r["kind"] == "shared_prefix"]
    assert len(shared) == round(mix["shared_prefix"]["share"] * mix["block"])
    heads = {tuple(r["prompt"][:mix["shared_prefix"]["tokens"]])
             for r in shared}
    assert 1 < len(heads) <= mix["shared_prefix"]["count"]


def test_open_schedule_falls_due_on_the_wall_clock_at_the_files_rate():
    mix = _mix("chat-open-0p8knee")
    sched = traffic.open_schedule(mix, 11, 50257, 200.0)
    due = [r["due_s"] for r in sched]
    assert due == sorted(due) and due[-1] <= 200.0
    assert len(sched) / 200.0 == pytest.approx(mix["rate_per_s"], rel=0.1)
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])


def test_a_mix_of_bursts_is_its_lengths_arriving_sixteen_at_a_time():
    # the mix a burst cell would add as a file (PERF.md section 7): the
    # chat mix with another arrival process and a lead-in between bursts
    chat = _mix("chat-open-0p8knee")
    burst = dict(chat, lead_in_s=6.0,
                 arrivals={"process": "bursts", "size": 16, "every_s": 4.0})
    sched = traffic.open_schedule(burst, BIG, 50257, 6.0 + 45.0)
    due = [r["due_s"] for r in sched]
    assert sorted(set(due)) == [4.0 * i for i in range(1, 13)]
    assert all(due.count(t) == 16 for t in set(due))
    # the window [lead-in, lead-in + 45) opens and closes between bursts,
    # so rounding of the clock never moves a burst across its edge
    lead = burst["lead_in_s"]
    assert all(abs(t - edge) >= 1.0 for t in set(due)
               for edge in (lead, lead + 45.0))
    assert sum(lead <= t < lead + 45.0 for t in due) == 176
    # the chat mix's lengths, block for block (bursts leave the gaps in
    # place, so the fixed order is another one)
    chat_reqs, burst_reqs = _take(chat, BIG, 64), _take(burst, BIG, 64)
    for lo in (0, 32):
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
            assert sorted(map(key, chat_reqs[lo:lo + 32])) == \
                sorted(map(key, burst_reqs[lo:lo + 32]))


def test_bursts_keep_the_mean_rate():
    mix = dict(_mix("chat-open-0p8knee"),
               arrivals={"process": "bursts", "size": 16, "every_s": 4.0})
    sched = traffic.open_schedule(mix, 5, 50257, 100.0)
    assert len(sched) / 100.0 == pytest.approx(4.0, rel=0.2)
    assert sum(r["gap_s"] == 0.0 for r in sched) >= len(sched) * 0.9


@pytest.mark.parametrize("dist,u,want", [
    ({"dist": "fixed", "value": 9}, 0.3, 9),
    ({"dist": "uniform", "min": 10, "max": 20}, 0.5, 15),
    ({"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
      "max": 1024}, 0.5, 192),
    ({"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
      "max": 1024}, 0.9999, 1024),
])
def test_quantiles_of_the_length_distributions(dist, u, want):
    assert traffic.quantile(dist, u) == want


def test_a_train_batch_is_fresh_every_step_and_seeded():
    a = np.random.default_rng(BIG)
    b = np.random.default_rng(BIG)
    first = traffic.train_batch(a, 4, 128, 50257)["input_ids"]
    assert first.shape == (4, 129) and first.dtype == np.int32
    assert np.array_equal(first, traffic.train_batch(b, 4, 128, 50257)[
        "input_ids"])
    assert not np.array_equal(first, traffic.train_batch(a, 4, 128, 50257)[
        "input_ids"])


def test_rehearse_overrides_lie_over_the_mix():
    full, tiny = _mix("longprompt-closed-16"), _mix("longprompt-closed-16",
                                                    rehearse=True)
    assert full["clients"] == 16 and tiny["clients"] < 16
    assert "rehearse" not in full and tiny["loop"] == "closed"
