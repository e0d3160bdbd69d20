"""Optimizer registry.

Reference: the basic-optimizer dispatch in DeepSpeedEngine
(runtime/engine.py:901 registry + :1141 _configure_basic_optimizer):
Adam/AdamW (torch or FusedAdam/CPUAdam), LAMB (FusedLamb), OnebitAdam,
OnebitLamb, ZeroOneAdam, Adagrad, SGD.

TPU-native: every optimizer is an optax ``GradientTransformation`` operating
on the fp32 master params (the model computes in bf16/fp16 via flax's dtype
casting — this replaces the reference's fp16 master-weight optimizers,
runtime/fp16/fused_optimizer.py). "Fused" variants resolve to the Pallas
fused kernels in deepspeed_tpu.ops (which exist to beat XLA's fused update
chain on HBM traffic for very large flat shards); a kernel that cannot be
built raises rather than giving way to optax.
"""

from typing import Callable, Optional, Union

import optax


ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
FUSED_ADAM = "fusedadam"
CPU_ADAM = "cpuadam"  # deepspeedcpuadam
LAMB_OPTIMIZER = "lamb"
FUSED_LAMB = "fusedlamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
ZERO_ONE_ADAM_OPTIMIZER = "zerooneadam"
ADAGRAD_OPTIMIZER = "adagrad"
SGD_OPTIMIZER = "sgd"

DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM, CPU_ADAM, LAMB_OPTIMIZER,
    FUSED_LAMB, ONEBIT_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER,
    ZERO_ONE_ADAM_OPTIMIZER, ADAGRAD_OPTIMIZER, SGD_OPTIMIZER,
]


def _adam_args(params):
    return dict(
        b1=params.get("betas", (0.9, 0.999))[0],
        b2=params.get("betas", (0.9, 0.999))[1],
        eps=params.get("eps", 1e-8),
    )


def build_optimizer(opt_type: str, params: dict,
                    lr_schedule: Optional[Union[float, Callable]] = None,
                    use_pallas: bool = True) -> optax.GradientTransformation:
    """Build the optax transform for a config ``optimizer`` block.

    ``lr_schedule`` overrides params["lr"] when given (engine wires the
    scheduler block here).
    """
    name = opt_type.lower().replace("deepspeed", "").replace("_", "")
    lr = lr_schedule if lr_schedule is not None else params.get("lr", 1e-3)
    wd = params.get("weight_decay", 0.0)

    if name in (ADAM_OPTIMIZER, FUSED_ADAM, CPU_ADAM):
        if name == FUSED_ADAM and use_pallas:
            from ..ops.pallas.fused_adam import fused_adamw
            return fused_adamw(lr, weight_decay=wd, **_adam_args(params))
        if wd > 0 and params.get("adam_w_mode", True):
            return optax.adamw(lr, weight_decay=wd, **_adam_args(params))
        tx = optax.adam(lr, **_adam_args(params))
        if wd > 0:  # plain Adam + L2 (reference adam_w_mode=False path)
            tx = optax.chain(optax.add_decayed_weights(wd), tx)
        return tx

    if name == ADAMW_OPTIMIZER:
        return optax.adamw(lr, weight_decay=wd, **_adam_args(params))

    if name in (LAMB_OPTIMIZER, FUSED_LAMB):
        if name == FUSED_LAMB and use_pallas:
            from ..ops.pallas.fused_lamb import fused_lamb
            return fused_lamb(lr, weight_decay=wd,
                              eps=params.get("eps", 1e-6),
                              b1=params.get("betas", (0.9, 0.999))[0],
                              b2=params.get("betas", (0.9, 0.999))[1])
        return optax.lamb(lr, weight_decay=wd, **_adam_args(params))

    if name == ADAGRAD_OPTIMIZER:
        return optax.adagrad(lr, eps=params.get("eps", 1e-10))

    if name == SGD_OPTIMIZER:
        return optax.sgd(lr, momentum=params.get("momentum", 0.0),
                         nesterov=params.get("nesterov", False))

    if name in (ONEBIT_ADAM_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER):
        # error-feedback momentum compression (reference: onebit/adam.py:10,
        # zoadam.py:10); the wire-level analog lives in
        # runtime/comm_compression.compressed_allreduce
        from .comm_compression import onebit_adam, zero_one_adam
        kw = dict(weight_decay=wd, **_adam_args(params))
        if name == ZERO_ONE_ADAM_OPTIMIZER:
            return zero_one_adam(
                lr, var_freeze_step=params.get("var_freeze_step", 100),
                var_update_scaler=params.get("var_update_scaler", 16), **kw)
        return onebit_adam(lr, freeze_step=params.get("freeze_step", 100),
                           **kw)

    if name == ONEBIT_LAMB_OPTIMIZER:
        from .comm_compression import onebit_lamb
        return onebit_lamb(lr, weight_decay=wd,
                           freeze_step=params.get("freeze_step", 100),
                           **_adam_args(params))

    raise ValueError(f"Unknown optimizer type '{opt_type}' "
                     f"(valid: {DEEPSPEED_OPTIMIZERS})")
