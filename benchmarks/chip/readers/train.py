"""Training's share of the chip's peak."""

from . import reader


@reader("mfu_pct")
def mfu_pct(obs):
    """Operations the forward and backward passes need per token (the
    family's count, stated by the runner: no recomputed operation
    counts) x tokens/s/chip of the traced run's untraced steps / peak
    bf16 FLOP/s."""
    f = obs.facts
    if "tokens_per_s_chip" not in f or "ops_per_token" not in f \
            or obs.peaks is None:
        return None
    return (100.0 * f["ops_per_token"] * f["tokens_per_s_chip"]
            / obs.peaks["bf16_flops_per_s"])
