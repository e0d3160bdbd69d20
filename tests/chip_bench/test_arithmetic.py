"""The yardstick's arithmetic on hand-made samples: percentiles, spreads
and rates; operations and bytes for gpt2-125m; the table of peaks."""

import json
import math
import os

import pytest

from benchmarks.chip import opcount, peaks
from benchmarks.chip.families import gpt2
from benchmarks.chip.stats import (beta_cdf, percentile, rate,
                                   samples_beyond, spread, tail,
                                   token_spans_ms)

from ._paths import BENCH


@pytest.mark.parametrize("values,q,want", [
    ([5, 1, 4, 2, 3], 50, 3),
    (list(range(1, 101)), 90, 90),
    (list(range(1, 11)), 90, 9),
    ([7], 90, 7),
    ([1, 2, 3, 4], 50, 2),
    ([1, 2, math.inf], 90, math.inf),
    ([1.0] * 95 + [math.inf] * 5, 90, 1.0),
    ([], 90, None),
])
def test_percentile_is_the_nearest_rank_on_the_sorted_samples(values, q, want):
    assert percentile(values, q) == want


@pytest.mark.parametrize("values,q,want", [
    ([1, 2], 50, 1.5),                  # Beta(1.5, 1.5) splits evenly
    ([7], 90, 7.0),
    (list(range(1, 11)), 50, 5.5),      # symmetric samples: the middle
    (list(range(1, 101)), 90, 90.5),
    ([3.0] * 40, 90, 3.0),
])
def test_tail_is_the_harrell_davis_percentile_of_all_samples(values, q, want):
    assert tail(values, q) == pytest.approx(want, rel=1e-8)
    assert tail([], q) is None


@pytest.mark.parametrize("times,span_ms,want", [
    # 0.1 s apart: three gaps span 250 ms; the last gap joins the last
    ([0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7], 250, [100.0, 100.0]),
    ([0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6], 250, [100.0, 100.0]),
    # a fast server: a stretch holds as many gaps as 250 ms take
    ([0.02 * i for i in range(27)], 250, [20.0, 20.0]),
    # shorter than one stretch: the whole request is one
    ([0.0, 0.1, 0.2], 250, [100.0]),
    # a stall lands in one stretch and leaves the others alone
    ([0.0, 0.1, 0.2, 0.3, 5.3, 5.4, 5.5, 5.6], 250,
     [100.0, 5000.0, 100.0]),
    ([0.0], 250, []),
    ([], 250, []),
])
def test_token_spans_cover_every_gap_once(times, span_ms, want):
    got = token_spans_ms(times, span_ms)
    assert got == pytest.approx(want)


def test_one_stall_is_the_tail_of_request_means_and_not_of_stretches():
    """150 requests of 65 tokens, 97 ms a token, 30 of them in flight
    when the server stalls for 10 s: the stall is in the mean of each of
    the 30, a fifth of all requests, so the 90th percentile of the means
    is the stall; of the stretches, 30 in 3,200 hold it."""
    def stamps(stalled):
        t, out = 0.0, []
        for i in range(65):
            out.append(t)
            t += 0.097 + (10.0 if stalled and i == 30 else 0.0)
        return out
    requests = [stamps(i < 30) for i in range(150)]
    means = [1e3 * (ts[-1] - ts[0]) / (len(ts) - 1) for ts in requests]
    stretches = [x for ts in requests for x in token_spans_ms(ts, 250)]
    assert tail(means, 90) > 200.0
    assert tail(stretches, 90) == pytest.approx(97.0, rel=1e-6)
    assert max(stretches) > 3000.0


def test_beta_cdf_against_known_values():
    # I_x(1, 1) = x;  I_x(2, 1) = x^2;  I_0.5(a, a) = 0.5
    assert beta_cdf(0.3, 1, 1) == pytest.approx(0.3)
    assert beta_cdf(0.3, 2, 1) == pytest.approx(0.09)
    assert beta_cdf(0.5, 70.5, 70.5) == pytest.approx(0.5)
    assert beta_cdf(0.3, 2.5, 7.1) == pytest.approx(0.6490225790210132)
    assert beta_cdf(0.0, 3, 4) == 0.0 and beta_cdf(1.0, 3, 4) == 1.0


def test_one_request_a_step_later_moves_the_tail_smoothly():
    """140 times to first token, 30 ms apart; the one at the p90 rank is
    taken one 95 ms server step later. Nearest rank jumps to its
    neighbour's time; the weighted tail moves by a ninth of the step."""
    before = [1000.0 + 30.0 * i for i in range(140)]
    after = list(before)
    after[125] += 95.0
    assert percentile(after, 90) - percentile(before, 90) == 30.0
    assert 0 < tail(after, 90) - tail(before, 90) < 12.0
    assert abs(tail(before, 90) - percentile(before, 90)) < 30.0


@pytest.mark.parametrize("n,q,want", [(100, 90, 10), (135, 90, 13),
                                      (10, 90, 1), (0, 90, 0)])
def test_samples_beyond_the_percentile(n, q, want):
    assert samples_beyond(n, q) == want


def test_spread_is_the_interquartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert spread([10.0] * 6) == 0.0


def test_rate_is_over_all_the_window_and_refuses_an_empty_one():
    assert rate(32768 * 3, 1.5) == 65536
    with pytest.raises(ValueError):
        rate(1, 0.0)


def test_ops_per_token_of_gpt2_125m_by_hand():
    # weights a token multiplies: 12 layers x 12 x 768^2 + 50257 x 768
    weights = 12 * 12 * 768 * 768 + 50257 * 768
    assert weights == 123_532_032
    # causal attention, forward + backward: 12 layers x 6 x 1024 x 768
    attention = 12 * 6 * 1024 * 768
    sizes = {"n_embd": 768, "n_layer": 12, "n_head": 12,
             "vocab_size": 50257, "n_positions": 1024}
    assert gpt2.ops_per_token(sizes, 1024) \
        == 6 * weights + attention == 797_815_296


@pytest.mark.parametrize("backward,ops,nbytes", [
    (False, 51_539_607_552, 202_899_456),
    (True, 103_079_215_104, 404_226_048),
])
def test_flash_ops_and_bytes_of_gpt2_125m_by_hand(backward, ops, nbytes):
    # batch 32, 12 heads, seq 1024, head 64, bf16
    assert opcount.flash_ops(32, 12, 1024, 64, backward) == ops
    assert opcount.flash_bytes(32, 12, 1024, 64, backward) == nbytes


def test_roofline_says_which_peak_bounds():
    v5e = peaks.peaks_for("TPU v5 lite")
    sec, bound = opcount.roofline_seconds(51_539_607_552, 202_899_456, v5e)
    assert bound == "compute"
    assert sec == pytest.approx(51_539_607_552 / 197e12)
    sec, bound = opcount.roofline_seconds(1e6, 819e9, v5e)
    assert bound == "memory" and sec == pytest.approx(1.0)


def test_peaks_of_the_v5e_are_the_published_ones():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "NVIDIA H100"])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(kind)
