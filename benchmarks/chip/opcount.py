"""Operations and bytes the algorithm needs, computed from shapes. Kept
with the benchmark so that no PR that claims a gain can change what a
share of the peak is a share of. A multiply-add counts as 2 operations;
recomputed operations (remat, the flash backward's second pass over the
scores) do not count. What a whole model needs per trained token is its
family's count (``families/<family>.py`` ``ops_per_token``)."""


def flash_ops(batch, heads, seq, head_dim, backward):
    """Operations of one causal flash-attention call. Forward: ``QK^T``
    and ``PV``, ``2 * seq^2 * head_dim`` each over the full square, half
    under the causal mask. Backward needs ``dV = P^T dO``, ``dP = dO
    V^T``, ``dQ = dS K`` and ``dK = dS^T Q``: four such products (the
    recomputed ``QK^T`` does not count)."""
    one = 2 * batch * heads * seq * seq * head_dim // 2
    return (4 if backward else 2) * one


def flash_bytes(batch, heads, seq, head_dim, backward, itemsize=2):
    """Bytes one flash-attention call must move to and from HBM if every
    operand is touched once: forward reads q, k, v and writes o (the
    log-sum-exp row is ``4 / (head_dim * itemsize)`` of one operand:
    counted); backward reads q, k, v, o, do and writes dq, dk, dv."""
    operand = batch * heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    return (8 * operand + lse) if backward else (4 * operand + lse)


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ("compute" if t_ops >= t_bytes else "memory")
