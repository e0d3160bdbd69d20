"""Numbers the program counted about itself: the process registry of
``deepspeed_tpu.observability.metrics`` (``get_registry()``), which
outlives the engine that wrote to it, so these readers find the counts
after ``srv.close()`` / ``engine.destroy()``. A name the program does
not write (an older program) reads as nothing, and the metric is left
out of the line."""

from . import reader
from ..stats import percentile


def _registry():
    from deepspeed_tpu.observability.metrics import get_registry
    return get_registry()


@reader("registry_percentile")
def registry_percentile(obs, histogram, q):
    """The ``q``-th percentile (``stats.percentile``) of the samples the
    histogram retains: the most recent 512 at the registry's default."""
    hist = _registry().histogram(histogram)
    values = list(hist.window)
    if not values:
        return None
    obs.say(f"{histogram}: {len(values)} samples retained of {hist.count}")
    return float(percentile(values, q))


@reader("registry_ratio_pct")
def registry_ratio_pct(obs, num, den):
    """100 x the sum of the counters ``num`` / the sum of the counters
    ``den`` (lists of names), over the life of the process."""
    reg = _registry()
    below = sum(reg.counter(name).value for name in den)
    if not below:
        return None
    above = sum(reg.counter(name).value for name in num)
    obs.say(f"{' + '.join(num)} = {above} of {' + '.join(den)} = {below}")
    return 100.0 * above / below
