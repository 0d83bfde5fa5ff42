"""Does the system still start on the chip?  GPT-2-medium at its published
width takes training steps through `paddle.jit.TrainStep` and answers
requests through `ServingEngine`, on one TPU chip, in one process.

    python chip_smoke.py             # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   # the dp=4 step against one device, only

One JSON object per phase, then as the LAST line of standard output

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

No phase is wrapped in try/except: a failure is a traceback and a non-zero
exit, and with no TPU the script exits before any phase.  Every time it
prints is a smoke reading (one run, compile included where it says so),
never a benchmark.  The compile cache goes where JAX_COMPILATION_CACHE_DIR
says, and otherwise to `.jax_cache` beside this file.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# The operating point of bench.py's GPT-2 leg: b4 s1024, bf16 autocast,
# dropout masks from the chip's own generator (threefry masks at these
# shapes cost the step ~16 ms and its compile about two minutes).
BATCH, SEQ, TRAIN_STEPS = 4, 1024, 6
SLOTS, MAX_LEN, NEW_TOKENS = 8, 1024, 32
# prompt lengths: 40 and 50 share the 64 bucket, 600 is past 512
PROMPT_LENS = (5, 40, 50, 100, 300, 600)
# bf16 autocast through an all-reduce: each chip sums its own quarter of the
# batch and the quarters are averaged, in another order than one chip sums the
# whole, so the losses (about 11) are not bit-equal.  The chip read 5.6e-5
# (PR 22); 1e-3 is twenty times that, and a collective that drops a shard or
# sums where it should average moves the second loss by more than 1e-2.
DP_LOSS_TOL = 1e-3


def check(ok, *facts):
    """An assert that `python -O` cannot remove."""
    if not ok:
        raise AssertionError(*facts)


def emit(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def attention_paths(since=None):
    """Attention calls traced so far by the form taken ({"flash": n,
    "xla": n}), less an earlier reading."""
    from paddle_tpu.observability.metrics import get_registry
    m = get_registry().get("attention_path_total")
    return {k[0]: v - (since or {}).get(k[0], 0) for k, v in m.samples()}


def compiled(step):
    """The one executable a warmed or called step holds."""
    (exe,) = step._compiled._exe.values()
    return exe


def build(cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu import models
    paddle.seed(seed)
    model = models.GPTForPretraining(cfg)
    crit = models.GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    return model, crit, opt


def fixed_batch(cfg, seed, batch, seq):
    import paddle_tpu as paddle
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq + 1)).astype("int32")
    return paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])


def run_steps(step, batch, n):
    """n steps on one batch, each ended by block_until_ready."""
    losses, seconds = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(*batch)
        loss._data.block_until_ready()
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, seconds


def train_phase(cfg, seed, batch=BATCH, seq=SEQ, steps=TRAIN_STEPS):
    import jax
    from paddle_tpu import programs
    from paddle_tpu.jit import TrainStep

    dev = jax.devices()[0]
    model, crit, opt = build(cfg, seed)
    step = TrainStep(model, lambda logits, label: crit(logits, label), opt,
                     amp_level="O1", amp_dtype="bfloat16")
    data = fixed_batch(cfg, seed, batch, seq)
    before = attention_paths()
    warm = step.warmup(*data)          # compiles, applies no update
    paths = attention_paths(since=before)
    check(paths.get("flash", 0) > 0 and paths.get("xla", 0) == 0, paths)
    kernel_in_step = "tpu_custom_call" in compiled(step).as_text()
    losses, seconds = run_steps(step, data, steps)
    check(all(np.isfinite(losses)), losses)
    check(losses[-1] < losses[0], losses)
    for name, p in model.state_dict().items():
        check({d.platform for d in p._data.devices()} == {dev.platform}, name)
    store = programs.store_stats()
    emit("train", model="gpt2-medium", layers=cfg.num_hidden_layers,
         hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
         vocab=cfg.vocab_size, batch=batch, seq=seq, amp="O1/bfloat16",
         params=int(sum(np.prod(p.shape) for p in model.parameters())),
         losses=losses, compile_seconds=warm["seconds"],
         step_seconds_smoke=seconds, attention_paths=paths,
         pallas_kernel_in_step=kernel_in_step,
         peak_bytes_in_use=(dev.memory_stats() or {}).get(
             "peak_bytes_in_use"),
         cache_dir=store["dir"], cache_hits=store["hits"],
         cache_misses=store["misses"])
    # the step dies here, and the optimizer state with it: serving needs
    # the room
    return model, kernel_in_step


def serve_phase(model, cfg, seed, slots=SLOTS, max_len=MAX_LEN,
                prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS):
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    model.eval()
    engine = ServingEngine(model, max_slots=slots, max_len=max_len)
    warm = engine.warmup()
    bound = len(engine.buckets) + 1
    check(engine.compile_counts()["total"] == bound, engine.compile_counts())
    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype("int32").tolist()
               for n in prompt_lens]
    before = attention_paths()
    t0 = time.perf_counter()
    responses = []
    for i, prompt in enumerate(prompts):
        responses.append(engine.submit(prompt, new_tokens))
        if i % 2:                      # later ones arrive mid-decode
            for _ in range(3):
                engine.step()
    engine.run_until_drained(timeout=600)
    total = time.perf_counter() - t0
    for r in responses:
        check(r.done() and r.error is None and r.finish_reason == "length", (
            r.request.id, r.finish_reason, r.error))
    tokens = [r.tokens() for r in responses]
    check(all(len(t) == new_tokens for t in tokens))
    counts = engine.compile_counts()
    check(counts["total"] == bound and engine.post_warmup_compiles() == 0, (
        counts, engine.post_warmup_compiles()))
    # the oracle: the same prompt alone through model.generate
    probe = 1
    solo, _ = model.generate(
        paddle.to_tensor(np.asarray(prompts[probe], np.int32)[None]),
        max_new_tokens=new_tokens)
    solo = np.asarray(solo.numpy())[0].tolist()
    check(tokens[probe] == solo, (tokens[probe], solo))
    engine.close()
    paths = attention_paths(since=before)
    emit("serve", model="gpt2-medium", layers=cfg.num_hidden_layers,
         hidden=cfg.hidden_size, slots=slots, max_len=max_len,
         buckets=list(engine.buckets), prompt_lens=list(prompt_lens),
         new_tokens=new_tokens, requests=len(responses),
         tokens_served=sum(len(t) for t in tokens),
         matches_solo_generate=True, compile_counts=counts,
         post_warmup_compiles=engine.post_warmup_compiles(),
         warmup_seconds=warm["seconds"],
         ttft_seconds_smoke=[r.ttft for r in responses],
         total_seconds_smoke=total, attention_paths_since_warmup=paths)


def dp_phase(cfg, seed, n_dev=4, per_dev_batch=2, seq=SEQ, steps=2):
    """The data-parallel step over `n_dev` chips against the same steps
    from the same seed on one device, in this one process.  Dropout is
    off in both: masks are drawn per device, so equal masks across two
    layouts are not something the system promises."""
    import jax
    from paddle_tpu import parallel
    from paddle_tpu.jit import TrainStep

    devices = jax.devices()[:n_dev]
    batch = per_dev_batch * n_dev

    def run(mesh):
        model, crit, opt = build(cfg, seed)
        loss_fn = lambda logits, label: crit(logits, label)  # noqa: E731
        if mesh is None:
            step = TrainStep(model, loss_fn, opt, amp_level="O1",
                             amp_dtype="bfloat16")
        else:
            strategy = parallel.DistributedStrategy(amp=True)
            step = parallel.ShardedTrainStep(model, loss_fn, opt,
                                             strategy=strategy, mesh=mesh)
        data = fixed_batch(cfg, seed, batch, seq)
        losses, seconds = run_steps(step, data, steps)
        return model, step, losses, seconds

    mesh = parallel.create_mesh({"dp": n_dev}, devices=devices)
    before = attention_paths()
    model, step, dp_losses, dp_seconds = run(mesh)
    paths = attention_paths(since=before)
    check(paths.get("flash", 0) > 0 and paths.get("xla", 0) == 0, paths)
    exe = compiled(step)
    ids_sharding = exe.input_shardings[0][-1][0]   # args -> batch -> ids
    check(ids_sharding.device_set == set(devices))
    check(ids_sharding.shard_shape((batch, seq)) == (per_dev_batch, seq))
    for name, p in model.state_dict().items():
        on = {d.id for d in p._data.devices()}
        check(on == {d.id for d in devices}, (name, on))
        check(p._data.sharding.is_fully_replicated, name)
    text = exe.as_text()
    check("all-reduce" in text)
    kernel_in_step = "tpu_custom_call" in text
    del model, step
    _, _, one_losses, one_seconds = run(None)
    check(all(np.isfinite(dp_losses + one_losses)))
    diffs = [abs(a - b) for a, b in zip(dp_losses, one_losses)]
    check(max(diffs) < DP_LOSS_TOL, (dp_losses, one_losses))
    emit("dp", model="gpt2-medium", layers=cfg.num_hidden_layers,
         hidden=cfg.hidden_size, mesh={"dp": n_dev}, batch=batch, seq=seq,
         dp_losses=dp_losses, one_device_losses=one_losses,
         max_abs_loss_diff=max(diffs), tolerance=DP_LOSS_TOL,
         batch_shards_on_devices=n_dev, params_replicated_on_devices=n_dev,
         all_reduce_in_step=True, attention_paths=paths,
         pallas_kernel_in_step=kernel_in_step,
         dp_step_seconds_smoke=dp_seconds,
         one_device_step_seconds_smoke=one_seconds)
    return kernel_in_step


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]             # raises where no backend starts
    if dev.platform != "tpu" or len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke needs {args.chips} TPU chip(s); JAX found "
                 f"{len(jax.devices())} x {dev.platform}")
    jax.config.update("jax_default_prng_impl", "rbg")

    from paddle_tpu import models, programs
    programs.enable(os.path.join(HERE, ".jax_cache"))
    if args.chips == 4:
        kernel_in_step = dp_phase(
            models.gpt2_medium_config(hidden_dropout_prob=0.0,
                                      attention_probs_dropout_prob=0.0),
            args.seed)
        check(kernel_in_step, "no pallas kernel in the compiled dp step")
    else:
        cfg = models.gpt2_medium_config()
        model, kernel_in_step = train_phase(cfg, args.seed)
        check(kernel_in_step, "no pallas kernel in the compiled train step")
        serve_phase(model, cfg, args.seed)
    # count: the chips this run used, not every chip the host shows
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
