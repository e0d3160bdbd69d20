"""deepspeed_tpu — a TPU-native training/inference framework with
DeepSpeed's capabilities (reference: jimwu6/DeepSpeed v0.7.0).

Public facade mirrors ``deepspeed/__init__.py``: ``initialize`` (:51),
``init_inference`` (:222), ``init_distributed``, ``add_config_arguments``
(:206). The engine returned by ``initialize`` is the TPU-native
DeepSpeedEngine (runtime/engine.py here vs runtime/engine.py:180 there).
"""

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .comm import init_distributed  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               *,
               loss_fn=None,
               sample_batch=None,
               rng=None,
               mesh=None):
    """Create a training engine (reference: deepspeed.initialize,
    deepspeed/__init__.py:51).

    Returns (engine, optimizer, dataloader, lr_scheduler) like the
    reference. TPU-specific inputs: ``loss_fn(model, params, batch, rng,
    train) -> loss``, ``sample_batch`` for shape-based init (or pass
    initialized flax variables via ``model_parameters``), optional ``mesh``.
    """
    from .runtime.engine import DeepSpeedEngine
    from .runtime.config import DeepSpeedConfig

    cfg = config if config is not None else config_params
    if cfg is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config:
        cfg = args.deepspeed_config
    if isinstance(cfg, str):
        import json
        with open(cfg) as f:
            cfg = json.load(f)
    if isinstance(cfg, dict):
        cfg = DeepSpeedConfig.from_dict(cfg)

    pipeline = False
    try:
        from .runtime.pipe.module import PipelineModule
        pipeline = isinstance(model, PipelineModule)
    except ImportError:
        pass

    if pipeline:
        if getattr(model, "heterogeneous", False):
            # heterogeneous LayerSpec stacks execute the 1F1B instruction
            # stream host-side (reference: _exec_schedule, pipe/engine.py
            # :1354); a mesh with a "data" axis composes DP with it
            # (stage params replicated, micros batch-sharded)
            from .runtime.pipe.host_engine import HostDrivenPipelineEngine
            engine = HostDrivenPipelineEngine(
                model, cfg, loss_fn=loss_fn, sample_batch=sample_batch,
                rng=rng, optimizer=optimizer, lr_scheduler=lr_scheduler,
                mesh=mesh, params=model_parameters)
        else:
            from .runtime.pipe.engine import PipelineEngine
            engine = PipelineEngine(model, cfg, loss_fn=loss_fn,
                                    sample_batch=sample_batch, rng=rng,
                                    mesh=mesh, optimizer=optimizer,
                                    lr_scheduler=lr_scheduler,
                                    params=model_parameters)
    else:
        engine = DeepSpeedEngine(model, cfg, loss_fn=loss_fn,
                                 params=model_parameters,
                                 sample_batch=sample_batch, rng=rng, mesh=mesh,
                                 optimizer=optimizer, lr_scheduler=lr_scheduler,
                                 mpu=mpu)

    dataloader = None
    if training_data is not None:
        from .runtime.dataloader import (DeepSpeedDataLoader,
                                         PrefetchingLoader)
        dataloader = PrefetchingLoader(DeepSpeedDataLoader(
            training_data,
            batch_size=engine.config.train_batch_size,
            collate_fn=collate_fn))
    return engine, engine.optimizer, dataloader, engine.lr_schedule


def init_inference(model=None, **kwargs):
    """Create an inference engine (reference: deepspeed/__init__.py:222)."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model, **kwargs)


def _lazy_exports():
    """Reference facade names (deepspeed/__init__.py:27-49) resolved on
    first use so importing the package stays light."""
    return {
        "zero": lambda: __import__(
            "deepspeed_tpu.runtime.zero", fromlist=["zero"]),
        "moe": lambda: __import__("deepspeed_tpu.moe", fromlist=["moe"]),
        "pipe": lambda: __import__(
            "deepspeed_tpu.runtime.pipe", fromlist=["pipe"]),
        "checkpointing": lambda: _from(
            "deepspeed_tpu.runtime.activation_checkpointing",
            "checkpointing"),
        "PipelineModule": lambda: _from(
            "deepspeed_tpu.runtime.pipe.module", "PipelineModule"),
        "LayerSpec": lambda: _from(
            "deepspeed_tpu.runtime.pipe.module", "LayerSpec"),
        "TiedLayerSpec": lambda: _from(
            "deepspeed_tpu.runtime.pipe.module", "TiedLayerSpec"),
        "OnDevice": lambda: _from(
            "deepspeed_tpu.utils.init_on_device", "OnDevice"),
        "DeepSpeedTransformerLayer": lambda: _from(
            "deepspeed_tpu.ops.transformer", "DeepSpeedTransformerLayer"),
        "DeepSpeedTransformerConfig": lambda: _from(
            "deepspeed_tpu.ops.transformer", "DeepSpeedTransformerConfig"),
        "log_dist": lambda: _from("deepspeed_tpu.utils.logging", "log_dist"),
    }


def _from(mod, name):
    return getattr(__import__(mod, fromlist=[name]), name)


def __getattr__(name):
    factory = _lazy_exports().get(name)
    if factory is None:
        raise AttributeError(f"module 'deepspeed_tpu' has no attribute {name!r}")
    value = factory()
    globals()[name] = value
    return value


def add_config_arguments(parser):
    """argparse integration (reference: deepspeed/__init__.py:206)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (always on; kept for parity)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed JSON config")
    group.add_argument("--local_rank", type=int, default=-1,
                       help="Local rank (launcher-provided; unused on TPU)")
    return parser
