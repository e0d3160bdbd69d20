"""Training's share of the chip's peak."""

from . import reader
from ..opcount import ops_per_token


@reader("mfu_pct")
def mfu_pct(obs):
    """Operations the forward and backward passes need per token (no
    recomputed operation counts) x tokens/s/chip of the traced run's
    untraced steps / peak bf16 FLOP/s."""
    f = obs.facts
    if "tokens_per_s_chip" not in f or obs.peaks is None:
        return None
    s = f["sizes"]
    ops = ops_per_token(s["n_embd"], s["n_layer"], s["vocab_size"], f["seq"])
    return 100.0 * ops * f["tokens_per_s_chip"] / obs.peaks["bf16_flops_per_s"]
