from .layer import MoE, ExpertMLP, DroplessMoE, is_moe_param
from .sharded_moe import (MOELayer, TopKGate, top1gating, top2gating,
                          topk_routing, dropless_experts, mean_gate,
                          load_balancing_loss)
