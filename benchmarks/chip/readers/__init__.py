"""Per-layer metric readers. A metric is a file ``metrics/<name>.json``
that names one of these by ``reader`` and gives its ``args``; a reader
takes what a run observed and returns the number, or ``None`` where
there was nothing to read (the harness then leaves the metric out of
the line). A later PR adds a reader by adding a module to this
directory: every module here is imported, and registers its functions
with ``@reader``."""

import importlib
import pkgutil

REGISTRY = {}


def reader(name):
    def register(fn):
        if name in REGISTRY:
            raise ValueError(f"two readers are named {name!r}")
        REGISTRY[name] = fn
        return fn
    return register


class Observed:
    """What readers read: ``setup`` (compile counts and seconds of
    set-up), ``series`` (host-clock samples by name, in ms), ``trace``
    (an ``xplane.Trace`` or None), ``peaks`` (the device's row of
    ``peaks.json``), ``facts`` (sizes and rates the runner states) and
    ``say`` (a line on standard output)."""

    def __init__(self, setup, series, trace, peaks, facts, say):
        self.setup, self.series, self.trace = setup, series, trace
        self.peaks, self.facts, self.say = peaks, facts, say


def load_all():
    for mod in pkgutil.iter_modules(__path__):
        importlib.import_module(f"{__name__}.{mod.name}")
    return REGISTRY
