"""The kernels the TPU branch can select, compiled for a described (not
attached) v5e chip at the widths they run at.  The chip's compiler refuses
things interpret mode accepts — a dot it cannot tile, a block that is not
aligned, too much fast memory — and this file is where that shows without
chip time.  Nothing runs: it says nothing about results or speed.

The only file that describes the chip.  The topology is described inside a
module-scoped fixture, never at import: one process at a time may load the
TPU's library, and every xdist worker imports every test file.
"""
import contextlib
import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import fused_bn_act as fbn
from paddle_tpu.ops import int8_matmul as i8
from paddle_tpu.ops import paged_attention as pa

# GPT-2-medium: 16 heads of 64, b4 s1024 under bf16 autocast
B, S, H, D = 4, 1024, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_compilation_cache():
    """The persistent compilation cache off: an executable for a described
    chip is written to it but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture()
def chip_compile(one_chip):
    """compile(fn, *(shape, dtype)) for the described chip, with the
    persistent compilation cache off; an argument may be a dict of such
    pairs, `donate` the arguments the program may write into."""
    def compile_(fn, *avals, donate=()):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip),
            list(avals), is_leaf=lambda a: isinstance(a, tuple))
        return jax.jit(fn, donate_argnums=donate).lower(
            *args).compile().as_text()

    with _no_compilation_cache():
        yield compile_


QKV = ((B, S, H, D), jnp.bfloat16)
SEED = ((1,), jnp.int32)


def flash(q, k, v, seed, causal, dropout_p):
    return fa._flash_bshd(q, k, v, None, None, None, seed, causal=causal,
                          dropout_p=dropout_p)


def flash_loss_grads(q, k, v, seed, causal, dropout_p):
    return jax.grad(lambda q, k, v: flash(
        q, k, v, seed, causal, dropout_p).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def test_flash_forward(chip_compile):
    text = chip_compile(functools.partial(flash, causal=False,
                                          dropout_p=0.0), QKV, QKV, QKV, SEED)
    assert text.count("tpu_custom_call") == 1


def flash_roofline_patterns():
    """The patterns by which the benchmark's `flash_attn_roofline` finds the
    two kernels' events in a trace: an event is named like its instruction."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "metrics", "flash_attn_roofline.json")
    with open(path) as f:
        params = json.load(f)["params"]
    return re.compile(params["forward"]), re.compile(params["backward"])


@pytest.mark.parametrize("b,s", [(B, S), (2, 2048)],
                         ids=["s1024_written_out", "s2048_loop"])
def test_flash_forward_backward_causal(chip_compile, b, s):
    """gpt2m-train's call (4 x 1024, 16 heads of 64: the walk to the
    diagonal written out) and one past `_WRITTEN_OUT` (the loop).  The
    compiled text holds exactly the custom calls the metric's two patterns
    name: the forward kernel and the merged backward."""
    qkv = ((b, s, H, D), jnp.bfloat16)
    text = chip_compile(functools.partial(flash_loss_grads, causal=True,
                                          dropout_p=0.0), qkv, qkv, qkv, SEED)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    forward, backward = flash_roofline_patterns()
    assert len(calls) == 2
    assert sum(bool(forward.search(c)) for c in calls) == 1
    assert sum(bool(backward.search(c)) for c in calls) == 1


@pytest.mark.parametrize("s,window", [(8192, None), (8192, 4096),
                                      (1024, 4096), (256, None)],
                         ids=["full_8192", "window_4096_of_8192",
                              "written_out_1024", "one_block_256"])
def test_flash_grouped_forward(chip_compile, monkeypatch, s, window):
    """command-a-plus's prefill attention (128 query heads on 8 KV heads of
    128, one prompt, bfloat16) through `flash_attention_grouped`: a full
    layer and a window layer of the longest bucket (the `fori_loop` walk,
    started at the window's edge), and the written-out and one-block walks
    of the short buckets.  One kernel, named by the scope the model calls
    it under (the prefill probe joins on it), fed K and V as they lie:
    8 heads wide, nothing repeated."""
    monkeypatch.setattr(fa, "_available", lambda: True)
    scope = "window_attention" if window else "full_attention"

    def attend(q, k, v):
        with jax.named_scope(scope):
            return fa.flash_attention_grouped(q, k, v, window=window)
    kv = ((1, s, 8, 128), jnp.bfloat16)
    text = chip_compile(attend, ((1, s, 128, 128), jnp.bfloat16), kv, kv)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].startswith(f"%{scope}")
    assert f'/{scope}/pallas_call"' in calls[0]
    assert calls[0].count(f"bf16[1,{s},1024]") == 2      # K and V


@pytest.mark.parametrize("hw_prng", [True, False],
                         ids=["hardware_prng", "hash_bits"])
def test_flash_dropout_forward_backward(chip_compile, monkeypatch, hw_prng):
    """In-kernel dropout through both bit-sources.  The program always takes
    the hardware PRNG on a chip; the hash form is the interpreter's, held to
    the same compiler so it stays a usable reference."""
    monkeypatch.setattr(fa, "_HW_PRNG", hw_prng)
    text = chip_compile(functools.partial(flash_loss_grads, causal=True,
                                          dropout_p=0.1), QKV, QKV, QKV, SEED)
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("m", [8, 512])
def test_int8_dequant_matmul(chip_compile, m):
    k, n = 1024, 4096                  # GPT-2-medium ffn_in
    text = chip_compile(i8._pallas_matmul, ((m, k), jnp.bfloat16),
                        ((k, n), jnp.int8), ((1, n), jnp.float32))
    assert "tpu_custom_call" in text


POOL = ((256, 16, H, D), jnp.bfloat16)  # blocks of 16 rows; 64 per slot


def test_paged_attention_one_slot(chip_compile):
    text = chip_compile(pa._pallas_paged_attention, ((H, D), jnp.bfloat16),
                        POOL, POOL, ((64,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_attention_vmapped_over_slots(chip_compile):
    fn = jax.vmap(pa._pallas_paged_attention, in_axes=(0, None, None, 0, 0))
    text = chip_compile(fn, ((8, H, D), jnp.bfloat16), POOL, POOL,
                        ((8, 64), jnp.int32), ((8,), jnp.int32))
    assert "tpu_custom_call" in text


def test_fused_bn_relu_forward_backward(chip_compile):
    """ResNet-50 stage 2 at b256 in NHWC: (256*28*28, 512) rows, bf16."""
    m, c = 256 * 28 * 28, 512
    blk_m = fbn._block_m(m, c)

    def loss_grads(x2, gamma, beta):
        return jax.grad(lambda x2, gamma, beta: fbn._bn_act_p(
            x2, gamma, beta, None, 1e-5, "relu", blk_m)[0].astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(x2, gamma, beta)

    text = chip_compile(loss_grads, ((m, c), jnp.bfloat16),
                        ((c,), jnp.float32), ((c,), jnp.float32))
    assert text.count("tpu_custom_call") >= 3   # stats, apply, backward


def test_held_experts_grouped_product_keeps_its_name(chip_compile):
    """command-a-plus's routed layer in a decode step (16 slots, 16 of 128
    experts held, 4096 wide, bfloat16): the chip's compiler takes
    `jax.lax.ragged_dot` as its own grouped kernel, three a layer, and their
    instructions carry the name by which `moe_expert_product_roofline` finds
    their events in a trace (and the metadata kernel beside each does not)."""
    from paddle_tpu.nn.functional.moe import GROUPED_PRODUCTS, moe_ffn_held
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "metrics", "moe_expert_product_roofline.json")
    with open(path) as f:
        kernel = re.compile(json.load(f)["params"]["kernel"])
    held, h = tuple(range(16)), 4096
    text = chip_compile(
        lambda x, r, g, u, d, valid: moe_ffn_held.raw(
            x, r, g, u, d, held, top_k=8, valid=valid),
        ((16, h), jnp.bfloat16), ((h, 128), jnp.float32),
        ((16, h, h), jnp.bfloat16), ((16, h, h), jnp.bfloat16),
        ((16, h, h), jnp.bfloat16), ((16,), jnp.bool_))
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    # as many as the program's spans say it made (`expert_products`)
    assert sum(bool(kernel.search(c)) for c in calls) == GROUPED_PRODUCTS
    assert len(calls) > GROUPED_PRODUCTS   # the group metadata: other names


def _moonlight_routed_layer(chip_compile, tokens):
    """(the compiled text of Moonlight's routed layer over `tokens` rows: 64
    experts of 2048 x 1408 all held, 6 a token, the selection bias and the
    scale, bfloat16; the pattern by which the cell's
    `moe_expert_product_roofline` finds a grouped product's events)."""
    from paddle_tpu.nn.functional.moe import moe_ffn_held
    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "benchmark", "metrics",
        "moe_expert_product_roofline.serve_flood_longgen.json")
    with open(path) as f:
        kernel = re.compile(json.load(f)["params"]["kernel"])
    held, h, i = tuple(range(64)), 2048, 1408
    text = chip_compile(
        lambda x, r, g, u, d, valid, bias: moe_ffn_held.raw(
            x, r, g, u, d, held, top_k=6, valid=valid, select_bias=bias,
            scale=2.446),
        ((tokens, h), jnp.bfloat16), ((h, 64), jnp.float32),
        ((64, h, i), jnp.bfloat16), ((64, h, i), jnp.bfloat16),
        ((64, i, h), jnp.bfloat16), ((tokens,), jnp.bool_),
        ((64,), jnp.float32))
    return text, kernel


def test_a_decode_step_that_hits_every_expert_makes_no_grouped_product(
        chip_compile):
    """Moonlight's decode step (48 slots x 6 picks over 64 experts): the
    batched form.  No instruction carries the grouped product's name, and
    the three batched products read each expert stack where it lies (no
    copy or transpose as large as one)."""
    text, kernel = _moonlight_routed_layer(chip_compile, 48)
    lines = [line.strip() for line in text.splitlines()]
    assert not [line for line in lines if kernel.search(line)]
    assert not [line for line in lines if re.search(
        r"\[64,(2048,1408|1408,2048)\]\S* (copy|transpose)\(", line)]
    assert sum("convolution(" in line and "moe_expert_product" in line
               for line in lines) == 3


def test_a_prompt_of_256_rows_keeps_the_grouped_products(chip_compile):
    """The same layer over the smallest prompt bucket: 256 rows are more
    than the batched form takes, so the three grouped products stand, under
    the name the metric reads."""
    from paddle_tpu.nn.functional.moe import GROUPED_PRODUCTS
    text, kernel = _moonlight_routed_layer(chip_compile, 256)
    assert sum(bool(kernel.search(line.strip()))
               for line in text.splitlines()) == GROUPED_PRODUCTS


def test_held_experts_walk_a_prompts_picks_in_chunks(chip_compile):
    """The same layer over a prompt's 2048 tokens (16,384 picks, an eighth
    of them held here): one `while` walks the held picks a chunk at a time,
    its body holds the three grouped products under the metric's name (an
    event a product a chunk, as the spans' `expert_products` count them),
    and nothing as wide as a row is left for all 16,384 picks."""
    from paddle_tpu.nn.functional.moe import GROUPED_PRODUCTS, moe_ffn_held
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "metrics", "moe_expert_product_roofline.json")
    with open(path) as f:
        kernel = re.compile(json.load(f)["params"]["kernel"])
    held, h, tokens, top_k = tuple(range(16)), 4096, 2048, 8
    text = chip_compile(
        lambda x, r, g, u, d, valid: moe_ffn_held.raw(
            x, r, g, u, d, held, top_k=top_k, valid=valid),
        ((tokens, h), jnp.bfloat16), ((h, 128), jnp.float32),
        ((16, h, h), jnp.bfloat16), ((16, h, h), jnp.bfloat16),
        ((16, h, h), jnp.bfloat16), ((tokens,), jnp.bool_))
    bodies = re.findall(r" while\(.*body=(%[\w.\-]+)", text)
    assert len(bodies) == 1
    body = text[text.index(f"\n{bodies[0]} ("):]
    body = body[:body.index("\n}\n")]
    calls = [line.strip() for line in body.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sum(bool(kernel.search(c)) for c in calls) == GROUPED_PRODUCTS
    assert sum(bool(kernel.search(line.strip()))
               for line in text.splitlines()) == GROUPED_PRODUCTS
    assert f"[{tokens * top_k},{h}]" not in text
    assert f"[{tokens},{top_k},{h}]" not in text


def test_moonlight_decode_layer_walks_the_live_rows(chip_compile,
                                                    monkeypatch):
    """One layer of Moonlight's decode step at 48 slots x 8192 rows (the
    attention at its published widths: 16 heads, a latent of 512 beside 64
    rotated numbers; a narrow MLP and vocabulary): the attention is ONE
    kernel under the name a trace shows it by, fed both leaves as they lie
    in the pool: no copy or transpose as large as the latent leaf."""
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import deepseek_v3 as M
    from paddle_tpu.ops import latent_decode_attention as K
    monkeypatch.setattr(K, "_available", lambda: True)
    model = M.DeepseekV3ForCausalLM(M.DeepseekV3Config(
        num_hidden_layers=1, vocab_size=256, intermediate_size=64))
    model.eval()
    state = {k: (tuple(v.shape), v._data.dtype)
             for k, v in model.state_dict().items()}

    def step(state, tokens, cbuf, pbuf, pos, active):
        logits, cache, counts = functional_call(
            model, state, tokens, [(cbuf, pbuf)], pos, active,
            training=False, method="forward_decode")
        return logits, cache, counts

    text = chip_compile(step, state, ((48,), jnp.int32),
                        ((48, 8192, 512), jnp.bfloat16),
                        ((48, 8192, 64), jnp.bfloat16), ((48,), jnp.int32),
                        ((48,), jnp.bool_), donate=(2, 3))
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 1
    assert calls[0].startswith("%mla_decode_attention")
    assert '/mla_decode_attention/' in calls[0]
    assert "bf16[48,8192,512]" in calls[0] and "bf16[48,8192,64]" in calls[0]
    assert not [line for line in text.splitlines() if re.search(
        r"bf16\[48,8192,512\]\S* (copy|transpose)\(", line.strip())]


@contextlib.contextmanager
def _engine_programs(one_chip, arch_name, config, traffic, narrow, bucket,
                     pool_leaves):
    """compile(name) -> the compiled form of the serving engine's OWN
    program (`decode`, `prefill_b<bucket>`) over a configuration of the
    benchmark at its PUBLISHED widths and its cell's slots, for the
    described chip, and the bytes the engine would hold (weights and cache).
    Nothing of that size exists here: the engine is built over a model cut
    by `narrow`, the model's config is then set to the published one (a
    forward reads its sizes from it, and `functional_call` swaps the
    leaves), and the programs are lowered on shapes alone: the benchmark's
    layout gives the weights', `pool_leaves(d, mix)` a cache layer's."""
    import importlib
    import sys
    from paddle_tpu.serving import ServingEngine
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness
    arch = importlib.import_module("benchmark.arch." + arch_name)
    with open(os.path.join(root, "benchmark", "configs", config)) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", traffic)) as f:
        mix = json.load(f)["engine"]
    program = cfg["program"]
    factory = harness.resolve(program["factory"])
    model = harness.resolve(program["model"])(
        factory(**dict(program["kwargs"], **narrow)))
    model.eval()
    eng = ServingEngine(model, max_slots=mix["max_slots"],
                        max_len=mix["max_len"],
                        prefill_buckets=(mix["prefill_buckets"][bucket],),
                        decode_chunk=mix["decode_chunk"])
    vars(model.config).update(vars(factory(**program["kwargs"])))
    d = arch.dims(cfg)
    shape = lambda s, dt: jax.ShapeDtypeStruct(  # noqa: E731
        tuple(s), dt, sharding=one_chip)
    state = {}
    for layer in range(-1, d["L"]):
        layout = (arch.top_layout(d) if layer < 0
                  else arch.layer_layout(d, d["kinds"][layer]))
        for name, leaf in layout.items():
            name = arch.program_name(name, layer)
            state[name] = shape(leaf[0], eng._state[name].dtype)
    assert set(state) == set(eng._state)
    pools = [tuple(shape(s, jnp.bfloat16)
                   for s in pool_leaves(d, mix))] * d["L"]
    programs = {name: (fn, inputs) for name, fn, inputs in eng._programs()}
    held = (sum(math.prod(leaf.shape) * 2 for layer in pools
                for leaf in layer) + arch.param_count(d) * 2)

    def compile_(name):
        fn, inputs = programs[name]
        with _no_compilation_cache():
            return fn.lower(
                {"model": state}, {"model": pools},
                jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                       inputs)).compile()

    try:
        yield compile_, held
    finally:
        eng.close()


@pytest.fixture(scope="module")
def moonlight_decode(one_chip):
    """(the compiled decode program of Moonlight's seven layers at 48 slots
    x 8192 rows with the decode kernel taken, which the CPU this runs on
    would refuse; the bytes held)."""
    from paddle_tpu.ops import latent_decode_attention as K
    at = lambda d, mix, w: (mix["max_slots"], mix["max_len"], w)  # noqa: E731
    available, K._available = K._available, lambda: True
    try:
        with _engine_programs(
                one_chip, "deepseek_v3", "moonlight-16b-a3b-7of27.json",
                "flood_longgen_8k.json",
                dict(hidden_size=64, intermediate_size=64,
                     moe_intermediate_size=32, vocab_size=256), 0,
                lambda d, mix: (at(d, mix, d["latent"]),
                                at(d, mix, d["rope"]))) as (compile_, held):
            return compile_("decode"), held
    finally:
        K._available = available


def test_moonlight_decode_program_compiles_and_fits(moonlight_decode,
                                                    record_property):
    """48 slots x 8192 latent rows x 7 layers beside 8.53 GB of weights:
    seven kernels a step under their name inside the decode loop, no copy
    or transpose of a latent leaf anywhere in the program, its temporaries
    beside what the engine holds inside the chip, and the module under the
    name `decode_flood_longgen_roofline` finds it by."""
    compiled, held = moonlight_decode
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    assert 11.6e9 < held < 11.8e9
    assert held + mem.temp_size_in_bytes < HBM
    assert mem.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    assert _metric_pattern("decode_flood_longgen_roofline",
                           "program").search(
        text.split(",", 1)[0].replace("HloModule ", "") + "(")
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 7
    assert all(c.startswith("%mla_decode_attention") for c in calls)
    assert not [line for line in text.splitlines() if re.search(
        r"bf16\[48,8192,512\]\S* (copy|transpose)\(", line.strip())]


@pytest.fixture(scope="module")
def evabyte_programs(one_chip):
    """compile(name) of EvaByte's `decode` and `prefill_b32768` at 16 slots
    x 32768 positions, and the bytes held."""
    def leaves(d, mix):
        row = (mix["max_slots"], d["window"], d["heads"], d["hd"])
        summary = row[:1] + (mix["max_len"] // d["chunk"],) + row[2:]
        return row, row, summary, summary

    with _engine_programs(
            one_chip, "evabyte", "evabyte-6.5b-8of32.json",
            "flood_longctx_32k.json",
            dict(hidden_size=64, intermediate_size=64), -1,
            leaves) as programs:
        yield programs


def _metric_pattern(name, key):
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "metrics", name + ".json")
    with open(path) as f:
        return re.compile(json.load(f)["params"][key])


HBM = 16 * 1024 ** 3 * 0.985        # what the compiler grants a program


def test_evabyte_decode_program_compiles_and_fits(evabyte_programs,
                                                  record_property):
    """16 slots x (2048 ring + 2048 summary rows) x 8 layers beside 3.26 GB
    of weights: the decode program's temporaries beside what the engine
    holds stay inside the chip, the rings and summaries are read where they
    lie (no copy or transpose as large as a leaf: a gather of a chunk's 16
    rows once re-laid the whole ring, 268 MB a leaf a step), and the module
    carries the name `decode_flood_longctx_roofline` finds it by."""
    compile_, held = evabyte_programs
    compiled = compile_("decode")
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    assert 11.8e9 < held < 11.9e9
    assert held + mem.temp_size_in_bytes < HBM
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    assert _metric_pattern("decode_flood_longctx_roofline",
                           "program").search(
        text.split(",", 1)[0].replace("HloModule ", "") + "(")
    assert not [line for line in text.splitlines() if re.search(
        r"bf16\[16,2048,32,128\]\S* (copy|transpose)\(", line.strip())]


def test_evabyte_longest_prefill_program_compiles_and_fits(
        evabyte_programs, monkeypatch, record_property):
    """The 32768 bucket: 16 windows a layer through the flash kernel's
    forward, each ONE custom call named by its scope (what
    `eva_prefill_attention_roofline.serve_flood_longctx` reads), 2048 + 128
    w rows long; the program hands back leaves no longer than the pool's
    and fits beside it."""
    monkeypatch.setattr(fa, "_available", lambda: True)
    compile_, held = evabyte_programs
    compiled = compile_("prefill_b32768")
    mem = compiled.memory_analysis()
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)
    assert held + mem.temp_size_in_bytes < HBM
    kernel = _metric_pattern(
        "eva_prefill_attention_roofline.serve_flood_longctx", "kernel")
    calls = [line.strip() for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 16 * 8 and all(kernel.search(c) for c in calls)
    for w in range(16):
        assert sum(f"bf16[1,{2048 + 128 * w},4096]" in c
                   for c in calls) == 8
