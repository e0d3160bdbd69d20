"""Host-clock samples taken by the runner in the traced run."""

from . import reader
from ..stats import percentile, tail


@reader("series_percentile")
def series_percentile(obs, series, q):
    values = obs.series.get(series)
    if not values:
        return None
    return float(percentile(values, q))


@reader("series_tail")
def series_tail(obs, series, q):
    """A tail of all the samples by the Harrell-Davis estimator."""
    values = obs.series.get(series)
    if not values:
        return None
    return float(tail(values, q))


@reader("peak_hbm_gb")
def peak_hbm_gb(obs):
    peak = obs.facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
