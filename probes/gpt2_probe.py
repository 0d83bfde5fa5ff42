"""GPT-2-medium TPU probe (VERDICT r4 item #2): batch and flash
block/group sweeps at s1024.  One config per process; serialize on the
chip.  PROBE <tag> <ms_per_step> <mfu>"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import gpt_train_flops  # noqa: E402  (single FLOPs accounting)


def main():
    tag = sys.argv[1]
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    import jax
    jax.config.update("jax_default_prng_impl", "rbg")
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    cfg = models.gpt2_medium_config()
    seq = 1024
    inner = models.GPTForPretraining(cfg)
    if tag.startswith("fused"):
        import paddle_tpu.nn as nn

        class FusedLM(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lm = inner

            def forward(self, ids, labels):
                return self.lm(ids, labels=labels)

        model = FusedLM()
        from paddle_tpu.tensor.stat import mean
        loss_fn = lambda per_tok, label: mean(per_tok)  # noqa: E731
    else:
        model = inner
        crit = models.GPTPretrainingCriterion()
        loss_fn = lambda logits, label: crit(logits, label)  # noqa: E731
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = TrainStep(model, loss_fn, opt, amp_level="O1",
                     amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    k = 5
    ids = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (k, batch, seq)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (k, batch, seq)).astype("int32"))
    args = ((ids, labels, labels) if tag.startswith("fused")
            else (ids, labels))
    for _ in range(2):
        losses = step.run_steps(*args)
    float(losses[-1])
    t0 = time.perf_counter()
    iters = 4
    for _ in range(iters):
        losses = step.run_steps(*args)
    float(losses[-1])
    dt = (time.perf_counter() - t0) / (iters * k)
    mfu = gpt_train_flops(batch, seq, cfg) / dt / 197e12 * 100
    print(f"PROBE {tag} {dt * 1e3:.2f} mfu={mfu:.2f} b={batch}", flush=True)


if __name__ == "__main__":
    main()
