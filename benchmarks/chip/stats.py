"""Arithmetic on samples: percentiles, spreads, window rates. Plain
Python, so that it is the same on every machine."""

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile by the nearest-rank rule on the sorted
    samples (no interpolation: a tail is a sample that was really seen).
    ``math.inf`` stands for a request that never got its token, so a tail
    is infinite as soon as more than ``100 - q`` percent are missing."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def _betacf(a, b, x):
    """Continued fraction of the incomplete beta function (Lentz)."""
    tiny = 1e-300
    qab, qap, qam = a + b, a + 1.0, a - 1.0
    c, d = 1.0, 1.0 - qab * x / qap
    d = 1.0 / (d if abs(d) > tiny else tiny)
    h = d
    for m in range(1, 400):
        m2 = 2 * m
        for num in (m * (b - m) * x / ((qam + m2) * (a + m2)),
                    -(a + m) * (qab + m) * x / ((a + m2) * (qap + m2))):
            d = 1.0 + num * d
            d = 1.0 / (d if abs(d) > tiny else tiny)
            c = 1.0 + num / c
            c = c if abs(c) > tiny else tiny
            h *= d * c
        if abs(d * c - 1.0) < 1e-14:
            break
    return h


def beta_cdf(x, a, b):
    """The regularized incomplete beta function ``I_x(a, b)``."""
    if x <= 0.0:
        return 0.0
    if x >= 1.0:
        return 1.0
    front = math.exp(math.lgamma(a + b) - math.lgamma(a) - math.lgamma(b)
                     + a * math.log(x) + b * math.log(1.0 - x))
    if x < (a + 1.0) / (a + b + 2.0):
        return front * _betacf(a, b, x) / a
    return 1.0 - front * _betacf(b, a, 1.0 - x) / b


def tail(values, q):
    """The ``q``-th percentile of *all* the samples by the Harrell-Davis
    estimator: the mean of the order statistics, each weighted by the
    share of a Beta((n+1)p, (n+1)(1-p)) distribution that falls in its
    1/n. Near the ranks about the percentile the weights are a few
    percent each, so the estimate moves smoothly where one sample changes
    rank. The nearest-rank percentile of some 140 requests is one
    request's time: in a server that steps every 95 ms it jumps by 2%
    when that request is taken one step sooner or later (PERF.md)."""
    if not values:
        return None
    s = sorted(values)
    n, p = len(s), q / 100.0
    a, b = (n + 1) * p, (n + 1) * (1.0 - p)
    edges = [beta_cdf(i / n, a, b) for i in range(n + 1)]
    return sum((edges[i + 1] - edges[i]) * s[i] for i in range(n))


def token_spans_ms(times, span_ms):
    """Milliseconds per token over consecutive stretches of one
    request's token stamps (seconds), each stretch the shortest that
    spans ``span_ms``; what is left at the end joins the last stretch.
    Every gap lies in exactly one stretch. The host's clock is off by
    some half a millisecond, so a single gap of a fast server is no
    measurement; a stretch of a quarter of a second is."""
    spans, start = [], 0
    for i in range(1, len(times)):
        if 1e3 * (times[i] - times[start]) >= span_ms:
            spans.append([times[i] - times[start], i - start])
            start = i
    left = len(times) - 1 - start
    if left > 0:
        if spans:
            spans[-1][0] += times[-1] - times[start]
            spans[-1][1] += left
        else:
            spans.append([times[-1] - times[start], left])
    return [1e3 * seconds / gaps for seconds, gaps in spans]


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def spread(values):
    """Interquartile distance as a share of the median — the contract's
    spread (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def rate(amount, seconds):
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} seconds")
    return amount / seconds
