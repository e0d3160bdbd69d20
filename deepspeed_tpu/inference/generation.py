"""Autoregressive generation with a preallocated KV cache.

Reference: the KV-cache attention path in DeepSpeedTransformerInference
(ops/transformer/inference/transformer_inference.py:732 — `layer_past`
handling) backed by the `softmax_context` CUDA kernel
(csrc/transformer/inference/csrc/pt_binding.cpp). The CUDA-graph
capture/replay of InferenceEngine (inference/engine.py:455/:474) maps to
one jitted decode step re-used across tokens.

TPU-first mechanics:
- the cache is preallocated at [batch, max_len, heads, head_dim] (stacked
  [L, ...] under nn.scan) and updated in place with
  ``lax.dynamic_update_slice`` — static shapes, one compile;
- the token loop is ``lax.scan`` over decode steps, entirely on device;
- prefill (the whole prompt in one forward) and decode (one token) are two
  cached jit specializations.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..observability.programs import track_program


def cache_shapes(module, params, batch_size: int, max_len: int):
    """The "cache" collection's shapes, by shape-only init."""
    ids = jnp.zeros((batch_size, max_len), jnp.int32)

    def mk(p):
        variables = module.init(jax.random.PRNGKey(0), ids, decode=True)
        return variables["cache"]
    return jax.eval_shape(mk, params)


def init_cache(module, params, batch_size: int, max_len: int):
    """Allocate the KV cache by shape-only init (no FLOPs burned)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shapes(module, params, batch_size, max_len))


def apply_decode(module, variables, ids, positions, live, mutable, **more):
    """``module.apply(variables, ids, decode=True, ...)`` as a serving
    program makes it: ``(logits, vars_out, counts)``.

    A module with an expert layer (``routes_tokens``, models/olmoe.py) is
    handed ``live()`` — ``[b, s]`` bool, the rows that hold a request's
    token — so that an idle slot or a chunk's padding is routed to no
    expert, and hands back its router's counts ``[L, E]``. For any other
    module this is the plain call: ``live`` is not called and ``counts``
    is None, an empty pytree that adds nothing to the program. A module
    that keeps a recurrent state and routes nothing (``masks_tokens``,
    models/falcon_h1.py) takes ``live()`` alone. ``more`` goes to the
    module as it is (a chunk program's ``positions_needed``)."""
    if not getattr(type(module), "routes_tokens", False):
        mask = ({"token_mask": live()}
                if getattr(type(module), "masks_tokens", False) else {})
        logits, vars_out = module.apply(
            variables, ids, decode=True, positions=positions,
            mutable=mutable, **mask, **more)
        return logits, vars_out, None
    (logits, router), vars_out = module.apply(
        variables, ids, decode=True, positions=positions, mutable=mutable,
        token_mask=live(), return_router=True, **more)
    return logits, vars_out, router["counts"]


def _prefill_impl(module, params, cache, input_ids, positions,
                  param_transform=None):
    if param_transform is not None:
        params = param_transform(params)
    logits, vars_out = module.apply(
        {"params": params, "cache": cache}, input_ids, decode=True,
        positions=positions, mutable=["cache"])
    return logits, vars_out["cache"]


_prefill = track_program(
    "inference/prefill", jax.jit(_prefill_impl, static_argnums=(0, 5)),
    subsystem="inference")
# generate() flows the cache linearly, so its entry copy can be donated —
# at serving scale the cache is GB-class and the duplicate costs real HBM
# headroom. Callers that deliberately REUSE a cache across calls (bench's
# percentile sampling, tests) use the non-donating _prefill/_decode_loop.
_prefill_donating = track_program(
    "inference/prefill_donating",
    jax.jit(_prefill_impl, static_argnums=(0, 5), donate_argnums=(2,)),
    subsystem="inference")


def _sampling_mode(temperature, top_k, top_p):
    """STRUCTURE (which sampling features are active) is compile-time;
    the VALUES stay traced so a temperature/top-k/top-p sweep reuses one
    executable (the engine.forward contract — weak #10 — applied to the
    decode loop). Concrete Python numbers decide the flags; traced
    inputs keep the feature on with the value as an operand."""
    greedy = isinstance(temperature, (int, float)) and temperature == 0.0
    has_k = top_k is not None and not (isinstance(top_k, int) and top_k <= 0)
    has_p = top_p is not None and not (
        isinstance(top_p, (int, float)) and top_p >= 1.0)
    t = jnp.float32(0.0 if temperature is None else temperature)
    k = jnp.int32(0 if top_k is None else top_k)
    p = jnp.float32(1.0 if top_p is None else top_p)
    return greedy, has_k, has_p, t, k, p


def _sample(logits, rng, temperature, top_k, top_p):
    """logits: [batch, vocab] -> [batch] token ids (values may be traced)."""
    greedy, has_k, has_p, t, k, p = _sampling_mode(temperature, top_k, top_p)
    return _sample_impl(logits, rng, t, k, p, greedy, has_k, has_p)


def _sample_impl(logits, rng, t, k, p, greedy, has_k, has_p):
    if greedy:
        return jnp.argmax(logits, axis=-1)
    raw = logits.astype(jnp.float32)
    # a TRACED temperature can still be 0.0 at runtime (the static
    # ``greedy`` flag only fires on concrete python numbers — the whole
    # point of keeping values traced is sweeping them over one
    # executable): dividing by it would make every logit inf and the
    # categorical sample NaN-garbage. Divide by a clamped value and
    # select argmax at the end instead — a runtime-zero temperature
    # degrades to greedy decoding, matching the static path.
    zero_t = t <= 0.0
    logits = raw / jnp.where(zero_t, jnp.float32(1.0), t)
    if has_k:
        # k-th largest via a traced slice into the ascending sort
        asc = jnp.sort(logits, axis=-1)
        kth = jax.lax.dynamic_slice_in_dim(
            asc, jnp.clip(asc.shape[-1] - k, 0, asc.shape[-1] - 1), 1,
            axis=-1)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if has_p:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p: keep logits >= cutoff
        keep = cum - probs < p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    sampled = jax.random.categorical(rng, logits, axis=-1)
    return jnp.where(zero_t, jnp.argmax(raw, axis=-1), sampled)


def _decode_loop_impl(module, params, cache, last_token, start_pos,
                      num_steps, t, k, p, rng, param_transform,
                      greedy, has_k, has_p):
    """Scan num_steps single-token forwards; returns [batch, num_steps]."""

    def step(carry, i):
        cache, token, pos = carry
        # transform INSIDE the body: int8 weights stay the resident copy;
        # the dequantized operands are step-transient (fused into the dots)
        p_ = param_transform(params) if param_transform is not None else params
        logits, vars_out = module.apply(
            {"params": p_, "cache": cache}, token[:, None], decode=True,
            positions=pos[None], mutable=["cache"])
        nxt = _sample_impl(logits[:, -1, :], jax.random.fold_in(rng, i),
                           t, k, p, greedy, has_k, has_p)
        return (vars_out["cache"], nxt, pos + 1), nxt

    (cache, _, _), tokens = jax.lax.scan(
        step, (cache, last_token, start_pos), jnp.arange(num_steps))
    return jnp.transpose(tokens), cache


_decode_jit = track_program(
    "inference/decode_loop",
    jax.jit(_decode_loop_impl, static_argnums=(0, 5, 10, 11, 12, 13)),
    subsystem="inference")
_decode_jit_donating = track_program(
    "inference/decode_loop_donating",
    jax.jit(_decode_loop_impl, static_argnums=(0, 5, 10, 11, 12, 13),
            donate_argnums=(2,)), subsystem="inference")


def _ragged_decode_loop_impl(module, params, cache, last_token, start_pos,
                             num_steps, t, k, p, rng, param_transform,
                             greedy, has_k, has_p):
    """Ragged twin of ``_decode_loop_impl``: ``start_pos`` is a PER-ROW
    [b] vector — each row appends at its own length (per-row cache_index,
    models/layers.py) and takes its own rotary/learned position. Kept as
    a separate jit so the shared-scalar hot path compiles unchanged."""
    from .cache import set_cache_index
    cache = set_cache_index(cache, start_pos)

    def step(carry, i):
        cache, token, pos = carry
        p_ = param_transform(params) if param_transform is not None else params
        logits, vars_out = module.apply(
            {"params": p_, "cache": cache}, token[:, None], decode=True,
            positions=pos[:, None], mutable=["cache"])
        nxt = _sample_impl(logits[:, -1, :], jax.random.fold_in(rng, i),
                           t, k, p, greedy, has_k, has_p)
        return (vars_out["cache"], nxt, pos + 1), nxt

    (cache, _, _), tokens = jax.lax.scan(
        step, (cache, last_token, start_pos), jnp.arange(num_steps))
    return jnp.transpose(tokens), cache


_ragged_decode_jit_donating = track_program(
    "inference/ragged_decode_loop",
    jax.jit(_ragged_decode_loop_impl, static_argnums=(0, 5, 10, 11, 12, 13),
            donate_argnums=(2,)), subsystem="inference")


def _decode_loop(module, params, cache, last_token, start_pos,
                 num_steps: int, temperature: float, top_k, top_p, rng,
                 param_transform=None, donate_cache: bool = False):
    greedy, has_k, has_p, t, k, p = _sampling_mode(temperature, top_k, top_p)
    fn = _decode_jit_donating if donate_cache else _decode_jit
    return fn(module, params, cache, last_token, start_pos, num_steps,
              t, k, p, rng, param_transform, greedy, has_k, has_p)


def _normalize_ragged_prompts(ids_np, prompt_lengths, pad_token_id):
    """Host-side padding normalization for the ragged path: returns
    (right-padded [b, Lmax] int array, lengths [b]). Accepts left- or
    right-padded rows when ``pad_token_id`` is given (padding must be one
    contiguous run at an end — the HF batch-encode convention); explicit
    ``prompt_lengths`` rows are taken as right-aligned at 0.

    Inference trims the pad RUN at one end (trailing run first), so
    pad-valued tokens *inside* or *leading* a prompt — e.g. BOS == pad —
    survive. The one irreducible ambiguity is a prompt that itself ENDS
    with the pad token: indistinguishable from padding, so pass
    ``prompt_lengths`` explicitly for those."""
    import numpy as np
    b, lmax = ids_np.shape
    if prompt_lengths is None:
        lengths = np.empty(b, np.int32)
        out = np.empty_like(ids_np)
        for i in range(b):
            row = ids_np[i]
            if row[-1] == pad_token_id:
                # right-padded: trim the trailing pad run (all-pad rows
                # degenerate to a single pad-token prompt)
                n = lmax
                while n > 1 and row[n - 1] == pad_token_id:
                    n -= 1
                seg = row[:n]
            else:
                # left-padded or unpadded: trim the leading pad run
                start = 0
                while start < lmax - 1 and row[start] == pad_token_id:
                    start += 1
                n = lmax - start
                seg = row[start:]
            lengths[i] = n
            out[i, :n] = seg
            out[i, n:] = pad_token_id
        return out, lengths
    lengths = np.asarray(prompt_lengths, np.int32)
    if lengths.shape != (b,):
        raise ValueError(f"prompt_lengths must be [batch]={b}, "
                         f"got shape {lengths.shape}")
    if (lengths < 1).any() or (lengths > lmax).any():
        raise ValueError("prompt_lengths must lie in [1, prompt width "
                         f"{lmax}], got {lengths.tolist()}")
    return ids_np, lengths


def generate(module, params, input_ids, *, max_new_tokens: int = 32,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None, rng: Optional[jax.Array] = None,
             eos_token_id: Optional[int] = None, max_len: Optional[int] = None,
             param_transform=None, prompt_lengths=None,
             pad_token_id: Optional[int] = None):
    """Generate continuations for a batch of prompts.

    Equal-length batches return [batch, prompt_len + max_new_tokens] token
    ids. ``eos_token_id`` tokens past the first EOS are replaced by EOS
    (the loop itself runs the full static length — XLA-friendly; the
    reference's python `while` loop would retrace per length).

    Ragged batches — pass ``prompt_lengths`` ([batch] true lengths of
    right-padded rows) and/or ``pad_token_id`` (lengths inferred; left- or
    right-padded rows accepted) — decode every row from its OWN length in
    one compiled program (per-row cache_index + positions; no host-side
    re-batching by length). Returns [batch, width + max_new_tokens] with
    each row ``prompt ++ generated ++ padding``.
    """
    input_ids = jnp.asarray(input_ids)
    if input_ids.ndim == 1:
        input_ids = input_ids[None]
    b, prompt_len = input_ids.shape

    if prompt_lengths is not None or pad_token_id is not None:
        import numpy as np
        ids_np, lengths = _normalize_ragged_prompts(
            np.asarray(input_ids), prompt_lengths, pad_token_id)
        return _generate_ragged(
            module, params, jnp.asarray(ids_np), lengths,
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, rng=rng, eos_token_id=eos_token_id,
            max_len=max_len, param_transform=param_transform,
            pad_token_id=pad_token_id)
    total = max_len or (prompt_len + max_new_tokens)
    if total < prompt_len + max_new_tokens:
        raise ValueError("max_len too small for prompt + max_new_tokens")
    model_max = getattr(getattr(module, "config", None), "max_seq_len", None)
    if model_max is not None and total > model_max:
        # jnp.take on the position table clips out-of-range indices, so
        # without this check decoding past the limit would silently reuse
        # the last position embedding instead of failing
        raise ValueError(
            f"prompt_len + max_new_tokens = {total} exceeds the model's "
            f"max_seq_len {model_max}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    # round the CACHE allocation up to a multiple of 128 so the Pallas
    # decode kernel's 128-aligned tiling always applies (slots past
    # `total` are never valid — the in-kernel length mask covers them)
    cache_len = (total + 127) // 128 * 128
    cache = init_cache(module, params, b, cache_len)
    logits, cache = _prefill_donating(module, params, cache, input_ids,
                                      jnp.arange(prompt_len),
                                      param_transform)
    first = _sample(logits[:, -1, :], rng, temperature, top_k, top_p)

    if max_new_tokens > 1:
        rest, cache = _decode_loop(
            module, params, cache, first, jnp.int32(prompt_len),
            max_new_tokens - 1, temperature, top_k, top_p,
            jax.random.fold_in(rng, 2**31), param_transform,
            donate_cache=True)
        out = jnp.concatenate([input_ids, first[:, None], rest], axis=1)
    else:
        out = jnp.concatenate([input_ids, first[:, None]], axis=1)

    if eos_token_id is not None:
        out = jnp.concatenate(
            [out[:, :prompt_len], _eos_fill(out[:, prompt_len:],
                                            eos_token_id)], axis=1)
    return out


def _eos_fill(gen, eos_token_id):
    """Replace everything after the first EOS with EOS ([b, n] -> [b, n])."""
    hit = jnp.asarray(gen == eos_token_id, jnp.int32)
    seen = jnp.cumsum(hit, axis=1) - hit
    return jnp.where(seen > 0, eos_token_id, gen)


def _generate_ragged(module, params, input_ids, lengths, *, max_new_tokens,
                     temperature, top_k, top_p, rng, eos_token_id, max_len,
                     param_transform, pad_token_id):
    """Unequal-length batch generation over one compiled program.

    ``input_ids`` [b, width] right-padded, ``lengths`` [b] host ints.
    Prefill runs once over the padded batch (pad rows are causally ahead
    of every valid token, so they cannot leak into valid logits); each
    row's first token is sampled from ITS last prompt position, then the
    per-row decode loop appends from each row's own length.
    """
    import numpy as np
    b, width = input_ids.shape
    total = max_len or (int(lengths.max()) + max_new_tokens)
    if total < int(lengths.max()) + max_new_tokens:
        raise ValueError("max_len too small for longest prompt + "
                         "max_new_tokens")
    model_max = getattr(getattr(module, "config", None), "max_seq_len", None)
    if model_max is not None and max(total, width) > model_max:
        raise ValueError(
            f"longest prompt + max_new_tokens = {total} (prompt width "
            f"{width}) exceeds the model's max_seq_len {model_max}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    lens = jnp.asarray(lengths, jnp.int32)

    # the cache must hold the full PADDED width too — prefill writes the
    # whole padded batch even though only [0, len_i) per row stays valid
    cache_len = (max(total, width) + 127) // 128 * 128
    cache = init_cache(module, params, b, cache_len)
    from .cache import has_recurrent_state
    if has_recurrent_state(cache):
        # K/V written by a row's padding is masked by its length; a
        # state it advanced is not
        raise NotImplementedError(
            f"ragged generate() of {type(module).__name__}: the prefill "
            "runs every row over the padded width, and a row's padding "
            "would write its recurrent (convolution) state; generate "
            "equal-length rows, or serve() it")
    logits, cache = _prefill_donating(module, params, cache, input_ids,
                                      jnp.arange(width), param_transform)
    last_logits = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0]        # [b, vocab]
    first = _sample(last_logits, rng, temperature, top_k, top_p)

    if max_new_tokens > 1:
        greedy, has_k, has_p, t, k, p = _sampling_mode(temperature, top_k,
                                                       top_p)
        rest, cache = _ragged_decode_jit_donating(
            module, params, cache, first, lens, max_new_tokens - 1,
            t, k, p, jax.random.fold_in(rng, 2**31), param_transform,
            greedy, has_k, has_p)
        gen = jnp.concatenate([first[:, None], rest], axis=1)
    else:
        gen = first[:, None]

    if eos_token_id is not None:
        gen = _eos_fill(gen, eos_token_id)

    fill = (pad_token_id if pad_token_id is not None
            else (eos_token_id if eos_token_id is not None else 0))
    out = jnp.concatenate(
        [input_ids, jnp.full((b, max_new_tokens), fill, input_ids.dtype)],
        axis=1)
    # place each row's generated run at ITS prompt length
    out = jax.vmap(
        lambda row, g, l: jax.lax.dynamic_update_slice(row, g, (l,)))(
        out, gen.astype(out.dtype), lens)
    # normalize the whole tail to ONE value: past [0, len+max_new) a row
    # otherwise holds leftover input padding followed by the fill —
    # mixed junk that a first-EOS-past-the-prompt scan would decode as
    # content. After this, every row is exactly prompt ++ gen ++ fill*.
    cols = jnp.arange(width + max_new_tokens)[None, :]
    out = jnp.where(cols >= (lens + max_new_tokens)[:, None],
                    jnp.asarray(fill, out.dtype), out)
    return out
