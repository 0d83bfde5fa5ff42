"""nemotron_h (Nemotron 3 Super): config keys -> sizes, the weights' layout BY
LAYER (a Mamba-2 mixer, LatentMoE or attention, as the pattern says), the
program's names, and the least operations and bytes of its programs.  A
layer's weights are made, cast and loaded one leaf at a time (`leaf_seed`),
as `arch/cohere2_moe.py` does and for its reason.

The cache: an attention layer holds a row of keys and values a token; a
Mamba layer holds a state of fixed size a slot, the SSM state (heads x
head_dim x state, float32) and the conv state (the last `conv - 1` xBC rows,
bfloat16), read and written whole every decode step.

Four Mamba leaves are not drawn as normal(0, std), as the source initialises
them (each under `assumed` in the configuration's file): `A_log` =
log(1..heads), `D` = 1, `dt_bias` the inverse softplus of a dt drawn
log-uniform in [time_step_min, time_step_max] and floored at
`time_step_floor`, and the conv's weight and bias normal with the variance
of PyTorch's default uniform init for 4 taps (std 0.5 / sqrt(3))."""
import math

import jax
import jax.numpy as jnp

from ..reference import nemotron_h as reference  # noqa: F401

CAUSAL = True
BF16, F32 = 2, 4
MAMBA, MOE, ATTENTION = "mamba", "moe", "attention"
_KINDS = {"M": MAMBA, "E": MOE, "*": ATTENTION}


def dims(cfg):
    L = cfg["num_hidden_layers"]
    held = list(cfg.get("experts_held", range(cfg["n_routed_experts"])))
    assert len(held) == cfg.get("num_experts_held", len(held))
    nh, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"V": cfg["vocab_size"], "H": cfg["hidden_size"], "L": L,
            "kinds": [_KINDS[c] for c in cfg["hybrid_override_pattern"][:L]],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "m_heads": nh, "m_hd": p, "groups": g, "state": n,
            "norm_groups": g,
            "d_inner": nh * p, "conv_dim": nh * p + 2 * g * n,
            "conv": cfg["conv_kernel"], "chunk": cfg["chunk_size"],
            "E": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
            "I": cfg["moe_intermediate_size"],
            "latent": cfg["moe_latent_size"],
            "SI": cfg["moe_shared_expert_intermediate_size"],
            "held": held, "scale": float(cfg["routed_scaling_factor"]),
            # no window: `rows_window` of the step records equals `rows_full`
            "window": cfg["max_position_embeddings"],
            "eps": cfg["norm_eps"], "std": cfg["initializer_range"],
            "bias_std": cfg["e_score_correction_bias_std"],
            "conv_std": cfg["conv_init_std"],
            "dt_range": (cfg["time_step_min"], cfg["time_step_max"],
                         cfg["time_step_floor"])}


def layer_layout(d, kind):
    """One layer's leaves beside its norm (scale 1): every matrix normal(0,
    std), the router's selection bias normal(0, bias_std); the Mamba leaves
    that `_special` makes are listed here with the kind "special"."""
    n, s, H = "normal", d["std"], d["H"]
    out = {"ln": ((H,), "ones", 0.0)}
    if kind == MAMBA:
        nh, di = d["m_heads"], d["d_inner"]
        out.update(in_proj=((H, di + d["conv_dim"] + nh), n, s),
                   conv_w=((d["conv"], d["conv_dim"]), n, d["conv_std"]),
                   conv_b=((d["conv_dim"],), n, d["conv_std"]),
                   A_log=((nh,), "special", 0.0), D=((nh,), "special", 0.0),
                   dt_bias=((nh,), "special", 0.0),
                   mnorm=((di,), "ones", 0.0), out_proj=((di, H), n, s))
    elif kind == ATTENTION:
        q, kv = d["heads"] * d["hd"], d["kv_heads"] * d["hd"]
        out.update(wq=((H, q), n, s), wk=((H, kv), n, s),
                   wv=((H, kv), n, s), wo=((q, H), n, s))
    else:
        held, lat, I = len(d["held"]), d["latent"], d["I"]
        out.update(router=((H, d["E"]), n, s),
                   bias=((d["E"],), n, d["bias_std"]),
                   e_up=((held, lat, I), n, s), e_down=((held, I, lat), n, s),
                   fc1=((H, lat), n, s), fc2=((lat, H), n, s),
                   s_up=((H, d["SI"]), n, s), s_down=((d["SI"], H), n, s))
    return out


def top_layout(d):
    return {"emb": ((d["V"], d["H"]), "normal", d["std"]),
            "lnf_g": ((d["H"],), "ones", 0.0),
            "head": ((d["H"], d["V"]), "normal", d["std"])}


def leaf_seed(seed, layer, leaf_no):
    """The seed `weights.make` gets for one leaf: from `--seed`, the layer's
    index (-1: the top level) and the leaf's place in its sorted layout."""
    return int(seed) * 4096 + (layer + 1) * 64 + leaf_no + 1


def _special(name, d, seed):
    """The Mamba leaves the source initialises otherwise (module
    docstring), float32."""
    nh = d["m_heads"]
    if name == "A_log":
        return jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32))
    if name == "D":
        return jnp.ones((nh,), jnp.float32)
    lo, hi, floor = d["dt_range"]
    from ..weights import seed_key
    u = jax.random.uniform(seed_key(seed), (nh,), jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo))
                             + math.log(lo)), floor)
    return dt + jnp.log(-jnp.expm1(-dt))                  # inverse softplus


def make_leaves(make, d, seed, layer):
    """Yields (reference leaf name, float32 array), one leaf at a time, for
    `layer` (an index, or -1 for the top level).  `make` is
    `benchmark.weights.make`."""
    layout = (top_layout(d) if layer < 0
              else layer_layout(d, d["kinds"][layer]))
    for k, name in enumerate(sorted(layout)):
        sub = leaf_seed(seed, layer, k)
        if layout[name][1] == "special":
            yield name, _special(name, d, sub)
        else:
            yield name, make({name: layout[name]}, sub)[name]


_TOP = {"emb": "embed_tokens", "lnf_g": "norm_f", "head": "lm_head"}
_LAYER = {"ln": "norm",
          "in_proj": "mixer.in_proj", "conv_w": "mixer.conv1d_weight",
          "conv_b": "mixer.conv1d_bias", "A_log": "mixer.A_log",
          "D": "mixer.D", "dt_bias": "mixer.dt_bias", "mnorm": "mixer.norm",
          "out_proj": "mixer.out_proj",
          "wq": "mixer.q_proj", "wk": "mixer.k_proj", "wv": "mixer.v_proj",
          "wo": "mixer.o_proj",
          "router": "mixer.experts.router",
          "bias": "mixer.experts.e_score_correction_bias",
          "e_up": "mixer.experts.up", "e_down": "mixer.experts.down",
          "fc1": "mixer.fc1_latent_proj", "fc2": "mixer.fc2_latent_proj",
          "s_up": "mixer.shared_up", "s_down": "mixer.shared_down"}


def program_name(ref, layer):
    """The program's state name of a reference leaf."""
    return _TOP[ref] if layer < 0 else f"layers.{layer}.{_LAYER[ref]}"


# ------------------------------------------------- operations and bytes

def _n(d, kind):
    return sum(k == kind for k in d["kinds"])


def mamba_params(d):
    """A Mamba layer's matrices (in_proj, out_proj), its conv, and its
    vectors."""
    return (d["H"] * (d["d_inner"] + d["conv_dim"] + d["m_heads"])
            + d["d_inner"] * d["H"] + (d["conv"] + 1) * d["conv_dim"]
            + 3 * d["m_heads"] + d["d_inner"])


def attn_params(d):
    return 2 * d["H"] * d["hd"] * (d["heads"] + d["kv_heads"])


def expert_params(d):
    """One routed expert: two matrices of latent x I (no gate)."""
    return 2 * d["latent"] * d["I"]


def moe_outside_params(d):
    """An E layer outside its routed experts: the router and its bias (kept
    float32), the two latent projections and the shared expert."""
    return ((d["H"] + 1) * d["E"] + 2 * d["H"] * d["latent"]
            + 2 * d["H"] * d["SI"])


def layer_params(d, kind):
    if kind == MAMBA:
        return mamba_params(d) + d["H"]
    if kind == ATTENTION:
        return attn_params(d) + d["H"]
    return moe_outside_params(d) + len(d["held"]) * expert_params(d) + d["H"]


def param_count(d):
    return (2 * d["V"] * d["H"] + d["H"]
            + sum(layer_params(d, k) for k in d["kinds"]))


def _token_params(d):
    """What one token multiplies, summed over layers: a Mamba layer's two
    projections, attention's four, and an E layer's router, latent
    projections, shared expert and its K experts at the held share (an
    even routing puts K x held / E of a token's picks here)."""
    routed = d["K"] * len(d["held"]) / d["E"] * expert_params(d)
    return (_n(d, MAMBA) * d["H"] * (2 * d["d_inner"] + d["conv_dim"]
                                    + d["m_heads"])
            + _n(d, ATTENTION) * attn_params(d)
            + _n(d, MOE) * (d["H"] * d["E"] + 2 * d["H"] * d["latent"]
                            + 2 * d["H"] * d["SI"] + routed))


def _scan_flops_per_token(d):
    """The Mamba scan a token a layer: the state's update (dt x (x) B, the
    decay) and C . h, about 2 x heads x head_dim x state each."""
    return 4.0 * d["d_inner"] * d["state"]


def prefill_flops(n, d):
    """Forward pass over a prompt of n tokens: the products of every token,
    the scan, attention's pairs under the causal mask, and the head at the
    last position alone."""
    pairs = n * (n + 1) / 2
    return (2.0 * n * _token_params(d)
            + _n(d, MAMBA) * n * _scan_flops_per_token(d)
            + _n(d, ATTENTION) * 4.0 * d["heads"] * d["hd"] * pairs
            + 2.0 * d["V"] * d["H"])


def decode_token_flops(rows, d, rows_window=None):
    """Forward pass of one token that attends `rows` cached rows in each
    attention layer.  (`rows_window` is the harness's second reading of the
    same rows: there is no window.)"""
    return (2.0 * _token_params(d)
            + _n(d, MAMBA) * _scan_flops_per_token(d)
            + _n(d, ATTENTION) * 4.0 * d["heads"] * d["hd"] * rows
            + 2.0 * d["V"] * d["H"])


def kv_row_bytes(d):
    """One cached row of one attention layer: a key and a value of every
    KV head, bfloat16 (1,024 at the published widths)."""
    return 2 * d["kv_heads"] * d["hd"] * BF16


def ssm_state_bytes(d):
    """One slot's state in one Mamba layer: the SSM state in float32 and
    the conv state in bfloat16 (4,255,744 at the published widths)."""
    return (d["m_heads"] * d["m_hd"] * d["state"] * F32
            + (d["conv"] - 1) * d["conv_dim"] * BF16)


def expert_bytes(d):
    """One routed expert's two matrices, bfloat16 (11,010,048)."""
    return expert_params(d) * BF16


def decode_weight_bytes(d):
    """What one decode step reads of the weights outside the routed experts
    at the least: every Mamba and attention layer, each E layer's router
    (float32), latent projections and shared expert, the norms, and the
    head (the embedding's rows are noise), bfloat16 but for the router."""
    return (_n(d, MAMBA) * (mamba_params(d) + d["H"]) * BF16
            + _n(d, ATTENTION) * (attn_params(d) + d["H"]) * BF16
            + _n(d, MOE) * ((d["H"] + 1) * d["E"] * F32
                            + (2 * d["H"] * d["latent"]
                               + 2 * d["H"] * d["SI"] + d["H"]) * BF16)
            + (d["H"] + d["V"] * d["H"]) * BF16)


def decode_call_cost(d, chunk, experts_hit, state_pool, rows_live):
    """The least bytes of one decode call of `chunk` dependent steps: each
    step reads the weights outside the routed experts once; a held expert
    only in the steps that routed a token to it (`experts_hit`, summed over
    layers and steps); each slot-layer of state the call went over
    (`state_pool`, summed so) is read and written; and the attention's live
    rows (`rows_live`, summed so) are read."""
    return float(chunk * decode_weight_bytes(d)
                 + experts_hit * expert_bytes(d)
                 + 2 * state_pool * ssm_state_bytes(d)
                 + rows_live * kv_row_bytes(d))


def expert_product_cost(picks, experts_hit, d):
    """(operations, bytes) of the grouped products at the least: two
    latent x I products a pick, and an expert's two matrices once where it
    was hit."""
    return (2.0 * picks * expert_params(d),
            float(experts_hit * expert_bytes(d)))
