"""Model families, found by name. A configuration file may carry
``"family"`` (absent means ``"gpt2"``); ``families/<family>.py`` is
imported by that name, as the modules under ``readers/`` are, and gives
the runners everything that is specific to an architecture:

``DEPTH_KEYS``
    the size keys that count layers (``("n_layer",)``): the only keys a
    configuration of the family may name in the manifest's ``reduced``,
    so that nothing but depth can be cut (``test_manifest.py``).
``sizes(config, rehearse)``
    the configuration's published sizes under the source's own key names
    (``vocab_size`` among them: the traffic generator draws tokens below
    it), or the file's ``rehearse`` sizes.
``build(config, rehearse, **overrides)``
    the ``deepspeed_tpu`` module that goes down the normal path
    (``ds.initialize`` / ``ds.init_inference(...).serve(...)``).
``train_loss(config)``
    the loss handed to ``ds.initialize`` (``kind: train`` only).
``reference_logits(params, ids, sizes, config)``,
``reference_next_token_losses(params, ids, sizes, config)``
    the plain float32 reference over the program's parameter tree; it
    shares no code with ``deepspeed_tpu`` and every caller runs it under
    ``reference.highest()``.
``ops_per_token(sizes, seq)``
    forward + backward operations per trained token, for
    ``train.mfu_pct`` (the runner states the count under ``facts``).
``expected_kernels(serving)``
    the ``tuning.last_dispatch`` records the warmed programs must have
    left (``check_kernels`` below): ``serving`` is the server's options,
    or ``None`` for a training cell.

A later PR adds an architecture by adding a file here; no runner names
one."""

import importlib

DEFAULT = "gpt2"

# what a record of a Pallas call compiled by Mosaic says (platform tpu):
# not the interpreter, and no fallback implementation
MOSAIC = {"interpret": False}
MOSAIC_KERNEL = {"interpret": False, "impl": "kernel"}


def load(config):
    """The family module of a configuration file."""
    name = config.get("family", DEFAULT)
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise KeyError(f"configuration names the family {name!r}: there is "
                       f"no families/{name}.py") from None


def check_kernels(last_dispatch, expected):
    """``expected`` is ``[(kernel, structure_prefix, fields)]``: at least
    one record of ``last_dispatch(kernel)`` has a structure that starts
    with the prefix, and every such record holds each of ``fields``. So
    the programs that were warmed ran the kernels the family names, and
    neither the interpreter nor a fallback path."""
    for kernel, prefix, fields in expected:
        records = {s: r for s, r in last_dispatch(kernel).items()
                   if s.startswith(prefix)}
        if not records:
            raise RuntimeError(
                f"{kernel}/{prefix}* was not dispatched (has: "
                f"{sorted(last_dispatch(kernel))})")
        for structure, rec in records.items():
            if any(k not in rec or rec[k] != v for k, v in fields.items()):
                raise RuntimeError(
                    f"{kernel}/{structure} is not {fields}: {rec}")
