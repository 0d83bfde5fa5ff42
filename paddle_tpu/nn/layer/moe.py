"""MoELayer — mixture-of-experts FFN block.

Beyond-reference: the reference snapshot has no MoE/expert parallelism
(SURVEY.md §2.3).  Expert weights carry a leading expert dim so
parallel.sharding.ep_spec can shard them P("ep", ...) when
DistributedStrategy.expert_parallel is on; the gate stays replicated.

Usage:
    moe = nn.MoELayer(d_model=256, d_hidden=1024, num_experts=8, top_k=2)
    y = moe(x)                       # x: (B, S, d_model)
    loss = task_loss + 0.01 * moe.aux_loss
"""
from __future__ import annotations

import jax

from ..layer_base import Layer
from .. import initializer as I
from ..functional.moe import moe_ffn, moe_ffn_held


class MoELayer(Layer):
    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", weight_attr=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        init = I.Normal(std=0.02)
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), attr=weight_attr,
            default_initializer=init)
        self.experts_w1 = self.create_parameter(
            (num_experts, d_model, d_hidden), default_initializer=init)
        self.experts_b1 = self.create_parameter(
            (num_experts, d_hidden), is_bias=True)
        self.experts_w2 = self.create_parameter(
            (num_experts, d_hidden, d_model), default_initializer=init)
        self.experts_b2 = self.create_parameter(
            (num_experts, d_model), is_bias=True)
        self.aux_loss = None

    def forward(self, x):
        y, aux = moe_ffn(x, self.gate_weight, self.experts_w1,
                         self.experts_b1, self.experts_w2, self.experts_b2,
                         top_k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         activation=self.activation)
        self.aux_loss = aux
        return y

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_hidden={self.d_hidden}, "
                f"num_experts={self.num_experts}, top_k={self.top_k}")


class HeldExperts(Layer):
    """The routed experts of one layer as ONE chip of an expert-parallel
    deployment holds them: the router over all `num_experts`, the gated
    (silu) weights of the experts in `experts_held` alone, no token dropped.

        y, picks_here, experts_hit, products, rows = layer(x)   # x: (T, d)

    `y` is the part of the routed sum that the held experts give
    (`functional.moe_ffn_held`); the four int32 counts (the last two: the
    grouped products made and the rows they went over, both 0 where the
    call took the batched form) are what a serving engine's spans and
    counters report.  Expert weights are created in
    `dtype`; the router stays float32 (its scores pick the experts).
    `selection_bias=True` adds the float32 leaf `e_score_correction_bias`
    (num_experts,), zeros: it moves which experts a token picks and not
    their weights; `scale` multiplies every weight.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int, experts_held=None, dtype=None,
                 std: float = 0.02, selection_bias: bool = False,
                 scale: float = None):
        super().__init__()
        self.experts_held = tuple(range(num_experts) if experts_held is None
                                  else (int(e) for e in experts_held))
        if (len(set(self.experts_held)) != len(self.experts_held)
                or not all(0 <= e < num_experts for e in self.experts_held)):
            raise ValueError(f"experts_held {self.experts_held} must be "
                             f"distinct ids below {num_experts}")
        self.num_experts, self.top_k, self.scale = num_experts, top_k, scale
        init, n = I.Normal(std=std), len(self.experts_held)

        def leaf(shape, dtype):
            # one leaf at a time: the initializer draws in float32, and the
            # draws of three such leaves in flight do not fit beside a
            # chip's share of a large model (PERF.md, PR 30)
            p = self.create_parameter(shape, dtype=dtype,
                                      default_initializer=init)
            jax.block_until_ready(p._data)
            return p

        self.router = leaf((d_model, num_experts), "float32")
        self.gate = leaf((n, d_model, d_hidden), dtype)
        self.up = leaf((n, d_model, d_hidden), dtype)
        self.down = leaf((n, d_hidden, d_model), dtype)
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), dtype="float32",
            default_initializer=I.Constant(0.0)) if selection_bias else None

    def forward(self, x, valid=None):
        return moe_ffn_held(x, self.router, self.gate, self.up, self.down,
                            self.experts_held, top_k=self.top_k, valid=valid,
                            select_bias=self.e_score_correction_bias,
                            scale=self.scale)

    def extra_repr(self):
        return (f"num_experts={self.num_experts}, top_k={self.top_k}, "
                f"held={len(self.experts_held)}")
