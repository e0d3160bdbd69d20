"""Set-up's compiles, from ``jax.monitoring`` (``phases.CompileLog``)."""

from . import reader


@reader("setup_field")
def setup_field(obs, field):
    """``compile_s``: backend compile seconds during set-up less the time
    spent reading cache entries back. ``compiles``: backend compiles
    that were not cache hits."""
    return float(obs.setup[field])
