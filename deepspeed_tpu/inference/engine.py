"""Inference engine.

Reference: deepspeed/inference/engine.py:27 InferenceEngine — wraps a model
for serving: dtype conversion, tensor-parallel group creation, kernel
injection, checkpoint loading, CUDA-graph capture, input broadcast.

TPU-native: the jitted decode step IS the captured graph (XLA compiles and
caches it — the analog of CUDA-graph capture/replay, engine.py:455/:474);
TP groups are the mesh's "model" axis; kernel injection swaps HF modules
for our fused flax modules (module_inject/).
"""

import contextlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .. import comm as dist
from ..utils.logging import logger, log_dist


class InferenceEngine:
    """Serve a flax model. Construct via ``deepspeed_tpu.init_inference``.

    Args (reference: init_inference kwargs, deepspeed/__init__.py:222):
        model: flax module (our models/ or an injected HF conversion)
        mp_size: tensor-parallel degree (mesh "model" axis size)
        dtype: compute dtype for serving
        replace_with_kernel_inject: swap HF layers for fused modules
        checkpoint: checkpoint path/dict to load
    """

    def __init__(self, model, mp_size: int = 1, dtype=jnp.bfloat16,
                 params=None, checkpoint=None,
                 replace_with_kernel_inject: bool = False,
                 injection_policy=None, max_tokens: int = 1024,
                 mesh=None, quantize_weights: bool = False,
                 quantize_min_size: int = 4096,
                 offload_params: bool = False, **kwargs):
        dist.init_distributed()
        # serving never fake-quantizes or constrains activations, and it
        # partitions over ITS mesh. Those tables and the global mesh are
        # process-global, so DON'T clear them (a concurrently-training
        # engine would silently lose them on its next retrace); instead
        # this engine's own traces run under _own_trace_state below — a
        # distillation teacher serves clean while the student keeps
        # training.
        self.module = model
        self.dtype = dtype
        self.mp_world_size = mp_size
        if mesh is None:
            mesh = dist.build_mesh(dist.MeshSpec(model=mp_size))
        elif isinstance(mesh, dist.MeshSpec):
            mesh = dist.build_mesh(mesh)
        self.mesh = mesh
        self.params = params
        self.checkpoint = checkpoint
        self.max_tokens = max_tokens
        self._injected = False
        self._compiled: Dict[str, Any] = {}
        self._param_transform = None

        # remember the architecture config + policy for checkpoint loading
        # (a raw HF state dict can't describe its own architecture)
        import flax.linen as nn
        self._hf_config = (None if isinstance(model, nn.Module)
                           else getattr(model, "config", model))
        self._injection_policy = injection_policy

        if replace_with_kernel_inject and model is not None:
            from ..module_inject.replace_module import replace_transformer_layer
            self.module, self.params = replace_transformer_layer(
                model, params=self.params, policy=injection_policy,
                dtype=dtype, mesh=mesh, checkpoint=checkpoint)
            self._injected = True

        if self.params is None and checkpoint is not None:
            self._load_checkpoint(checkpoint)
        elif (self.params is not None and not self._injected
              and isinstance(model, nn.Module)
              and dist.mp_world_size(mesh) > 1):
            # caller-supplied weights get the same tensor-parallel
            # placement as converted/loaded ones; left alone they would
            # sit whole on the first device and mp_size would be inert
            from flax.core import meta
            from ..module_inject.replace_module import \
                shard_params_for_inference
            self.params = shard_params_for_inference(
                self.module, meta.unbox(self.params), mesh, None)

        if quantize_weights:
            # Weight-only int8 serving (reference: module_quantize.py +
            # the *_int8 inference gemms): big 2D+ params stored int8 with
            # per-channel scales; dequant fuses into the decode matmuls.
            if self.params is None:
                raise ValueError(
                    "quantize_weights=True needs params (pass params= or "
                    "checkpoint=)")
            # direct-vs-transform consumption decided by the module's
            # supports_quantized_kernels capability flag — the shared
            # checkpoint->int8 pipeline step (module_quantize.py,
            # also the serving engine's serving.quantize.weights path)
            from ..module_inject.module_quantize import (
                quantize_for_serving, quantized_nbytes)
            self.params, self._param_transform = quantize_for_serving(
                self.module, self.params, min_size=quantize_min_size,
                dtype=dtype)
            nb = quantized_nbytes(self.params)
            log_dist(
                f"int8 weight-only quantization: "
                f"{nb['quantized']/1e6:.1f}MB vs "
                f"{nb['dense_equivalent']/1e6:.1f}MB dense", ranks=[0])

        self._zero_inference = False
        if offload_params:
            # ZeRO-Inference (reference: DeepSpeedZeRoOffload standalone
            # for inference, runtime/zero/parameter_offload.py:166):
            # weights larger than HBM live in the accelerator host's
            # memory and stream per layer through the decode scan. The
            # per-token cost is host-link-bandwidth-bound — the mode
            # trades latency for model size (serve bf16 models whose
            # weights alone exceed the chip).
            from ..utils.streaming import ensure_streaming_module
            self.module = ensure_streaming_module(
                self.module, context="offload_params serving")
            if self.params is not None:
                self.params = self._place_offloaded(self.params)
            self._zero_inference = True
            log_dist("ZeRO-Inference: block params in host memory, "
                     "streamed per layer through the decode scan",
                     ranks=[0])

    @staticmethod
    def _place_offloaded(params):
        """Host-place the stacked block KERNELS (>=3-D leaves of "h");
        bias/scale leaves (KB-scale) plus embeddings and the final norm
        stay device-resident — the reference's persistence-threshold
        semantics, and required on TPU (host-space scan xs with ndim<3
        leaves hit XLA layout bugs; see models/gpt.py offload branch)."""
        import jax
        from ..utils.streaming import to_host_tree
        from flax.core import meta as _meta
        params = dict(_meta.unbox(params))
        if "h" not in params:
            raise ValueError(
                "offload_params serving expects scan-stacked block params "
                f"under 'h'; got keys {sorted(params)}")
        params["h"] = jax.tree.map(
            lambda a: (to_host_tree(a) if getattr(a, "ndim", 0) >= 3
                       else jax.device_put(a, jax.memory.Space.Device)),
            params["h"])
        return params

    @contextlib.contextmanager
    def _own_trace_state(self):
        """This engine's mesh as the global mesh and no training-side
        rule tables, for the programs it traces."""
        from ..comm.mesh import global_mesh_scope
        from ..models.layers import training_rules_suspended
        with global_mesh_scope(self.mesh), training_rules_suspended():
            yield

    def _load_checkpoint(self, checkpoint):
        from ..module_inject.load_checkpoint import load_model_checkpoint
        self.params = load_model_checkpoint(
            self.module, checkpoint, self.mesh, dtype=self.dtype,
            policy=self._injection_policy, hf_config=self._hf_config)

    def forward(self, *args, **kwargs):
        """Jitted module forward (compiled once per shape — the XLA analog
        of CUDA-graph replay). Only genuinely structural kwargs (bools,
        strings, None — decode, deterministic, ...) are compile-time
        constants; numeric scalars like a temperature are TRACED so a
        sweep of values reuses one executable (weak #10: the old
        hasattr-shape heuristic recompiled per float)."""
        static = {k: v for k, v in kwargs.items()
                  if isinstance(v, (bool, str)) or v is None}
        arrays = {k: v for k, v in kwargs.items() if k not in static}
        key = ("forward", tuple(sorted(static.items())))
        if key not in self._compiled:
            module, transform = self.module, self._param_transform
            from ..observability.programs import track_program
            statics = ",".join(f"{k}={v}" for k, v in sorted(static.items()))
            self._compiled[key] = track_program(
                f"inference/forward[{statics}]",
                jax.jit(
                    lambda p, a, kw: module.apply(
                        {"params": transform(p) if transform else p},
                        *a, **kw, **static)),
                subsystem="inference")
        with self._own_trace_state():
            return self._compiled[key](self.params, args, arrays)

    __call__ = forward

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        """Greedy/sampled generation with a preallocated KV cache
        (reference: the KV-cache attention kernels, softmax_context).

        The cache is sized to the engine's ``max_tokens`` (reference:
        init_inference(max_tokens=...)), so repeated calls with different
        prompt lengths reuse one compiled decode loop."""
        from .generation import generate as _generate
        import numpy as np
        width = np.shape(input_ids)[-1]
        prompt_lengths = kwargs.get("prompt_lengths")
        pad_only_ragged = (prompt_lengths is None
                           and kwargs.get("pad_token_id") is not None)
        if prompt_lengths is not None:
            # ragged batch: the request size is the LONGEST TRUE prompt,
            # not the padded width (width alone would falsely reject
            # legal batches whose padding pushes width+max_new over the
            # model limit)
            prompt_len = int(np.max(np.asarray(prompt_lengths)))
        else:
            prompt_len = width
        model_max = getattr(getattr(self.module, "config", None),
                            "max_seq_len", None)
        # pad-only ragged mode: true lengths are unknown until generation
        # normalizes the padding — its own per-row checks are
        # authoritative, and it sizes the cache itself
        if not pad_only_ragged:
            needed = prompt_len + max_new_tokens
            cache_len = max(self.max_tokens, needed)
            if model_max is not None:
                if needed > model_max:
                    # refuse up front with the request arithmetic spelled
                    # out — clamping the cache here would silently
                    # truncate the generation instead
                    raise ValueError(
                        f"prompt_len ({prompt_len}) + max_new_tokens "
                        f"({max_new_tokens}) = {needed} exceeds the "
                        f"model's max_seq_len {model_max}; shorten the "
                        "prompt or reduce max_new_tokens")
                # clamp the preallocated cache to the model limit (the
                # request itself fits — only the engine's max_tokens
                # headroom shrinks)
                cache_len = min(cache_len, model_max)
            kwargs.setdefault("max_len", cache_len)
        kwargs.setdefault("param_transform", self._param_transform)
        with self._own_trace_state():
            return _generate(self.module, self.params, input_ids,
                             max_new_tokens=max_new_tokens, **kwargs)

    def serve(self, config=None, **kwargs):
        """Continuous-batching serving over this engine's module/params
        (slot-based KV cache, request queue — see docs/serving.md).
        ``config`` is a ``serving.ServingConfig`` or dict; extra kwargs
        override individual knobs."""
        from ..serving.engine import ServingEngine
        srv = ServingEngine(self.module, self.params, config,
                            param_transform=self._param_transform,
                            trace_scope=self._own_trace_state, **kwargs)
        # every width a prefill chunk may take exists before the first
        # request: a width met first under load would compile there
        srv.compile_chunk_programs()
        return srv
