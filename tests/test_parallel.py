"""Distributed-layer tests on the virtual 8-device CPU mesh
(SURVEY.md §4 implication (c): the reference runs 2-rank subprocesses and
compares against numpy/single-rank — here SPMD runs on 8 virtual devices and
is compared against the single-device eager result)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import parallel
from paddle_tpu import models


def test_create_mesh_axes():
    mesh = parallel.create_mesh({"dp": 2, "tp": 4})
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mesh.shape["pp"] == 1 and mesh.shape["sp"] == 1
    with pytest.raises(ValueError):
        parallel.create_mesh({"bogus": 2})
    with pytest.raises(ValueError):
        parallel.create_mesh({"dp": 64})


def test_strategy_mesh_axes():
    st = parallel.DistributedStrategy(tensor_parallel=True)
    st.hybrid_configs.mp_degree = 4
    assert st.mesh_axes(8) == {"dp": 2, "pp": 1, "ep": 1, "tp": 4, "sp": 1}
    st2 = parallel.DistributedStrategy()
    assert st2.mesh_axes(8)["dp"] == 8


def test_tp_specs():
    mesh = parallel.create_mesh({"tp": 4, "dp": 2})
    specs = parallel.param_specs(
        {"blocks.0.qkv.weight": (32, 96), "blocks.0.qkv.bias": (96,),
         "blocks.0.proj.weight": (32, 32), "blocks.0.ln1.weight": (32,),
         "word_embeddings.weight": (128, 32)},
        mesh, tensor_parallel=True)
    assert specs["blocks.0.qkv.weight"] == P(None, "tp")
    assert specs["blocks.0.qkv.bias"] == P("tp")
    assert specs["blocks.0.proj.weight"] == P("tp", None)
    assert specs["word_embeddings.weight"] == P("tp", None)
    assert specs["blocks.0.ln1.weight"] == P()


def test_fsdp_specs():
    mesh = parallel.create_mesh({"dp": 2, "tp": 4})
    spec = parallel.apply_fsdp(P(None, "tp"), (32, 96), mesh)
    assert spec == P("dp", "tp")
    spec = parallel.apply_fsdp(None, (128, 32), mesh)
    assert spec == P("dp", None)
    # non-divisible dims stay unsharded
    spec = parallel.apply_fsdp(None, (33,), mesh)
    assert spec is None or spec == P(None)


def _train_ref(model_fn, batches, lr=1e-2):
    """Single-device eager reference trajectory."""
    paddle.seed(123)
    model, crit = model_fn()
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=model.parameters())
    losses = []
    for ids, labels in batches:
        logits = model(paddle.to_tensor(ids))
        loss = crit(logits, paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


def _gpt_tiny(n_layers=2):
    cfg = models.GPTConfig(vocab_size=64, hidden_size=32,
                           num_hidden_layers=n_layers,
                           num_attention_heads=4, max_position_embeddings=32,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0)
    return models.GPTForPretraining(cfg), models.GPTPretrainingCriterion()


def _gpt_tiny4():
    return _gpt_tiny(n_layers=4)


def _batches(n=3, b=8, s=16, vocab=64):
    rng = np.random.RandomState(0)
    return [(rng.randint(0, vocab, (b, s)).astype("int32"),
             rng.randint(0, vocab, (b, s)).astype("int32"))
            for _ in range(n)]


@pytest.mark.parametrize("axes,st_kw", [
    ({"dp": 8}, {}),
    ({"dp": 2, "tp": 4}, {"tensor_parallel": True}),
    ({"dp": 4}, {"sharding": True}),   # ZeRO-3/FSDP
])
def test_sharded_step_matches_single_device(axes, st_kw):
    batches = _batches()
    ref = _train_ref(_gpt_tiny, batches)

    paddle.seed(123)
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    st = parallel.DistributedStrategy(**st_kw)
    if st.sharding:
        st.sharding_configs.stage = 3
    if st.tensor_parallel:
        st.hybrid_configs.mp_degree = 4
    mesh = parallel.create_mesh(axes)
    step = parallel.ShardedTrainStep(
        model, lambda logits, label: crit(logits, label), opt,
        strategy=st, mesh=mesh)
    losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
              for ids, labels in batches]
    np.testing.assert_allclose(losses, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kw", [{"mesh": "m"}, {"batch_sharding": "dp"}])
def test_one_device_step_refuses_a_mesh_and_names_the_sharded_step(kw):
    """jit.TrainStep used to accept mesh= / batch_sharding= and ignore
    them (a 'dp' run on one device); now the caller is told where to go."""
    from paddle_tpu.jit import TrainStep
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    if "mesh" in kw:
        kw = {"mesh": parallel.create_mesh({"dp": 8})}
    with pytest.raises(TypeError, match="parallel.ShardedTrainStep"):
        TrainStep(model, lambda logits, label: crit(logits, label), opt,
                  **kw)


def test_fsdp_params_actually_sharded():
    paddle.seed(0)
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    st = parallel.DistributedStrategy(sharding=True)
    st.sharding_configs.stage = 3
    mesh = parallel.create_mesh({"dp": 8})
    step = parallel.ShardedTrainStep(
        model, lambda l, y: crit(l, y), opt, strategy=st, mesh=mesh)
    step.place_params()
    w = model.gpt.blocks[0].qkv.weight._data
    shard_shape = w.sharding.shard_shape(w.shape)
    assert shard_shape != tuple(w.shape), "FSDP left params replicated"


def test_gradient_merge_matches_large_batch():
    """k_steps microbatches must equal one big-batch step (GradientMerge)."""
    batches = _batches(n=2, b=8)
    ref = _train_ref(_gpt_tiny, batches)

    paddle.seed(123)
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    st = parallel.DistributedStrategy(gradient_merge=True)
    st.gradient_merge_configs.k_steps = 4
    mesh = parallel.create_mesh({"dp": 2})
    step = parallel.ShardedTrainStep(
        model, lambda l, y: crit(l, y), opt, strategy=st, mesh=mesh)
    losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
              for ids, labels in batches]
    # loss returned is the last microbatch's; just check training progressed
    # identically enough: compare final params to reference run
    np.testing.assert_allclose(losses[-1], ref[-1], rtol=5e-2, atol=5e-2)


def test_recompute_matches():
    batches = _batches(n=2)
    ref = _train_ref(_gpt_tiny, batches)
    paddle.seed(123)
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    st = parallel.DistributedStrategy(recompute=True)
    step = parallel.ShardedTrainStep(
        model, lambda l, y: crit(l, y), opt, strategy=st,
        mesh=parallel.create_mesh({"dp": 2}))
    losses = [float(step(paddle.to_tensor(i), paddle.to_tensor(l)))
              for i, l in batches]
    np.testing.assert_allclose(losses, ref, rtol=2e-3, atol=2e-3)


def test_collectives_under_shard_map():
    """Reference pattern: test_collective_base.py compares 2-rank c_* op
    output to numpy; here: 8-rank shard_map vs numpy."""
    from jax import shard_map
    from paddle_tpu.distributed import collective as C
    mesh = parallel.create_mesh({"dp": 8})
    x = np.arange(32, dtype=np.float32).reshape(8, 4)

    def allreduce_rank(xs):
        t = C.all_reduce(paddle.Tensor(xs[0]), axis_name="dp")
        return t._data[None]

    out = shard_map(allreduce_rank, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out)[0], x.sum(0))

    def gather_rank(xs):
        lst = []
        C.all_gather(lst, paddle.Tensor(xs[0]), axis_name="dp")
        return jnp.stack([t._data for t in lst])[None]

    out = shard_map(gather_rank, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None, None))(x)
    np.testing.assert_allclose(np.asarray(out)[0], x)

    def bcast_rank(xs):
        t = C.broadcast(paddle.Tensor(xs[0]), src=3, axis_name="dp")
        return t._data[None]

    out = shard_map(bcast_rank, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out)[0], x[3])

    def permute_rank(xs):
        t = C.ppermute(paddle.Tensor(xs[0]), shift=1, axis_name="dp")
        return t._data[None]

    out = shard_map(permute_rank, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(x, 1, axis=0))

    def rs_rank(xs):
        t = C.reduce_scatter(None, paddle.Tensor(xs[0]), axis_name="dp")
        return t._data[None]

    x8 = np.arange(64, dtype=np.float32).reshape(8, 8)
    out = shard_map(rs_rank, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None))(x8)
    np.testing.assert_allclose(np.asarray(out).reshape(8), x8.sum(0))


def test_collectives_eager_single_process():
    """World of one: collectives are identity (paddle semantics preserved)."""
    from paddle_tpu.distributed import collective as C
    t = paddle.to_tensor(np.ones((4,), "float32"))
    out = C.all_reduce(t)
    np.testing.assert_allclose(out.numpy(), np.ones(4))
    lst = []
    C.all_gather(lst, t)
    assert len(lst) == 1


def test_pipeline_parallel_matches_single_device():
    """GPipe over pp=4 (+dp=2) must track the single-device trajectory
    (reference: PipelineOptimizer + SectionWorker microbatch schedule)."""
    from paddle_tpu.parallel.pipeline import gpt_pipeline_step

    batches = _batches(n=3, b=8, s=16)
    ref = _train_ref(_gpt_tiny, batches)

    paddle.seed(123)
    model, crit = _gpt_tiny()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    mesh = parallel.create_mesh({"dp": 2, "pp": 2})
    step = gpt_pipeline_step(model, opt, mesh, n_micro=2, remat=True)
    losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
              for ids, labels in batches]
    np.testing.assert_allclose(losses, ref, rtol=5e-3, atol=5e-3)
    # params written back match enough to produce the same logits
    step.sync_to_model()
    model.eval()
    ids = batches[0][0]
    logits = model(paddle.to_tensor(ids))
    assert np.isfinite(logits.numpy()).all()


def test_ring_attention_matches_naive():
    """Ring attention over sp=4 (+dp=2) vs the naive full-seq softmax;
    forward AND gradients (the backward ring falls out of autodiff)."""
    from paddle_tpu.ops.ring_attention import ring_attention

    rng = np.random.RandomState(3)
    b, s, h, d = 2, 32, 4, 8
    q = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))

    def naive(q, k, v, causal):
        qt, kt, vt = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
        sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(mask, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)

    mesh = parallel.create_mesh({"dp": 2, "sp": 4})
    for causal in (False, True):
        ref = naive(q, k, v, causal)
        out = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # gradient parity
        g_ref = jax.grad(lambda q, k, v: naive(q, k, v, causal).sum(),
                         argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(
            lambda q, k, v: ring_attention(q, k, v, mesh,
                                           causal=causal).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for gr, gg in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=2e-3, atol=2e-3)


def test_fleet_facade():
    from paddle_tpu.distributed import fleet
    st = parallel.DistributedStrategy(tensor_parallel=True)
    st.hybrid_configs.mp_degree = 2
    fleet.init(is_collective=True, strategy=st)
    mesh = parallel.get_mesh()
    assert mesh is not None and mesh.shape["tp"] == 2
    assert fleet.worker_num() == 1 and fleet.is_first_worker()

    model, crit = _gpt_tiny()
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(learning_rate=1e-2,
                              parameters=model.parameters()))
    step = fleet.distributed_train_step(model, lambda l, y: crit(l, y), opt)
    ids, labels = _batches(n=1)[0]
    loss = step(paddle.to_tensor(ids), paddle.to_tensor(labels))
    assert np.isfinite(float(loss))
    parallel.set_mesh(None)


def test_data_parallel_wrapper():
    model, _ = _gpt_tiny()
    dp = paddle.distributed.DataParallel(model)
    ids = paddle.to_tensor(_batches(n=1)[0][0])
    model.eval()
    out = dp(ids)
    assert out.shape[0] == 8
    assert len(dp.parameters()) == len(model.parameters())


def _spawn_worker():
    import os
    assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    assert rank in (0, 1)


def test_spawn_multiprocess_smoke():
    """Reference pattern: test_dist_base forks subprocess trainers; here we
    spawn 2 CPU procs that each check their rank env."""
    from paddle_tpu.distributed.spawn import spawn
    spawn(_spawn_worker, nprocs=2, port=29786)


def test_adamw_decay_fn_eager_autoname():
    """apply_decay_param_fun must work on the eager path WITHOUT manual
    naming (regression: params had name=None so the fn was ignored)."""
    lin = paddle.nn.Linear(4, 4)
    assert lin.bias.name is not None and "bias" in lin.bias.name
    opt = paddle.optimizer.AdamW(
        learning_rate=0.1, weight_decay=0.5, beta1=0.0, beta2=0.0,
        parameters=lin.parameters(),
        apply_decay_param_fun=lambda n: "bias" not in n)
    before = lin.bias.numpy().copy()
    for p in lin.parameters():
        p.grad = paddle.to_tensor(np.zeros(p.shape, "float32"))
    opt.step()
    np.testing.assert_allclose(lin.bias.numpy(), before, atol=1e-7)
    # layernorm weight excluded by "norm" marker
    ln = paddle.nn.LayerNorm(4)
    assert "norm" in ln.weight.name


def test_p2p_pairs():
    from jax import shard_map
    from paddle_tpu.distributed import collective as C
    mesh = parallel.create_mesh({"dp": 8})
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def body(xs):
        t = C.p2p(paddle.Tensor(xs[0]), pairs=[(1, 5)], axis_name="dp")
        return t._data[None]

    out = np.asarray(shard_map(body, mesh=mesh, in_specs=P("dp", None),
                               out_specs=P("dp", None))(x))
    assert out[5, 0] == 1.0 and out[0, 0] == 0.0

    def sendbody(xs):
        t = C.send(paddle.Tensor(xs[0]), dst=3, axis_name="dp")
        return t._data[None]

    with pytest.raises(Exception):
        shard_map(sendbody, mesh=mesh, in_specs=P("dp", None),
                  out_specs=P("dp", None))(x)


def test_allreduce_prod_signs_and_zeros():
    from jax import shard_map
    from paddle_tpu.distributed import collective as C
    mesh = parallel.create_mesh({"dp": 8})
    x = np.array([[-2.0], [3.0], [1.0], [-1.0], [2.0], [1.0], [1.0], [1.0]],
                 np.float32)

    def body(xs):
        return C.all_reduce(paddle.Tensor(xs[0]), op=C.ReduceOp.PROD,
                            axis_name="dp")._data[None]

    out = np.asarray(shard_map(body, mesh=mesh, in_specs=P("dp", None),
                               out_specs=P("dp", None))(x))
    np.testing.assert_allclose(out[0], 12.0)  # (-2)*3*(-1)*2 = 12
    x0 = x.copy(); x0[2] = 0.0
    out = np.asarray(shard_map(body, mesh=mesh, in_specs=P("dp", None),
                               out_specs=P("dp", None))(x0))
    np.testing.assert_allclose(out[0], 0.0)


def test_pipeline_1f1b_matches_single_device():
    """The hand-scheduled 1F1B (recompute backward, bounded stash) must
    track the same trajectory as single-device eager — the strongest check
    that the manual vjp schedule computes the true gradient."""
    from paddle_tpu.parallel.pipeline import gpt_pipeline_step

    batches = _batches(n=3, b=8, s=16)
    ref = _train_ref(_gpt_tiny4, batches)

    paddle.seed(123)
    model, crit = _gpt_tiny4()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    mesh = parallel.create_mesh({"dp": 2, "pp": 4})
    # n_micro=4 > p-1: exercises warmup, steady 1F1B interleave and drain
    step = gpt_pipeline_step(model, opt, mesh, n_micro=4, remat=True,
                             schedule="1f1b")
    losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
              for ids, labels in batches]
    np.testing.assert_allclose(losses, ref, rtol=5e-3, atol=5e-3)


def test_pipeline_1f1b_matches_gpipe_grads():
    """1F1B and GPipe are the same math in a different order: from the same
    init, one step must produce (near-)identical losses."""
    from paddle_tpu.parallel.pipeline import gpt_pipeline_step
    ids, labels = _batches(n=1, b=8, s=16)[0]
    losses = {}
    for sched in ("gpipe", "1f1b"):
        paddle.seed(7)
        model, crit = _gpt_tiny()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        mesh = parallel.create_mesh({"pp": 2})
        step = gpt_pipeline_step(model, opt, mesh, n_micro=4, remat=False,
                                 schedule=sched)
        l1 = float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
        l2 = float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
        losses[sched] = (l1, l2)
    np.testing.assert_allclose(losses["gpipe"], losses["1f1b"],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_pipeline_1f1b_peak_memory_below_gpipe():
    """The 1F1B design claim (pipeline.py:25-31) measured: XLA buffer
    assignment must give 1F1B a lower peak temp allocation AND a smaller
    per-microbatch growth than GPipe (whose autodiff backward stores the
    whole fwd trajectory)."""
    from paddle_tpu.parallel.pipeline import gpt_pipeline_step

    def peak(sched, n_micro):
        paddle.seed(5)
        model, crit = _gpt_tiny4()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        mesh = parallel.create_mesh({"dp": 2, "pp": 4})
        step = gpt_pipeline_step(model, opt, mesh, n_micro=n_micro,
                                 remat=True, schedule=sched)
        ids = np.zeros((n_micro * 2, 16), "int32")
        return step.memory_stats(paddle.to_tensor(ids),
                                 paddle.to_tensor(ids))["temp_bytes"]

    g8, f8 = peak("gpipe", 8), peak("1f1b", 8)
    g16, f16 = peak("gpipe", 16), peak("1f1b", 16)
    assert f8 < g8 and f16 < g16
    # trajectory term: GPipe's growth with n_micro strictly exceeds 1F1B's
    assert (g16 - g8) > (f16 - f8)


def test_pipeline_respects_frozen_params():
    from paddle_tpu.parallel.pipeline import gpt_pipeline_step
    paddle.seed(3)
    model, crit = _gpt_tiny()
    frozen = model.gpt.blocks[0].qkv.weight
    frozen.stop_gradient = True
    frozen.trainable = False
    before = frozen.numpy().copy()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    mesh = parallel.create_mesh({"pp": 2})
    step = gpt_pipeline_step(model, opt, mesh, n_micro=2, remat=False)
    ids, labels = _batches(n=1, b=4)[0]
    step(paddle.to_tensor(ids), paddle.to_tensor(labels))
    step.sync_to_model()
    # whole qkv.weight stack is frozen-mixed -> per-suffix rule freezes all;
    # at minimum the frozen layer must be unchanged
    np.testing.assert_allclose(model.gpt.blocks[0].qkv.weight.numpy(),
                               before, atol=1e-7)


def test_data_parallel_eager_reducer_parity():
    """Real eager DDP (imperative/reducer.h:116 parity): wrapping a model in
    DataParallel shards batch inputs over the dp mesh axis, eager ops run
    SPMD, and grads arrive identical to the single-device run on the same
    global batch."""
    def build():
        paddle.seed(7)
        return paddle.nn.Sequential(
            paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
            paddle.nn.Linear(16, 4))

    ref = build()
    ddp_inner = build()
    mesh = parallel.create_mesh({"dp": 8})
    ddp = paddle.distributed.DataParallel(ddp_inner, mesh=mesh)

    opt_ref = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=ref.parameters())
    opt_ddp = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=ddp.parameters())
    rng = np.random.RandomState(0)
    for _ in range(3):
        x = rng.randn(16, 8).astype("float32")
        y = rng.randn(16, 4).astype("float32")

        out_r = ref(paddle.to_tensor(x))
        loss_r = paddle.mean((out_r - paddle.to_tensor(y)) ** 2)
        loss_r.backward()
        opt_ref.step()
        opt_ref.clear_grad()

        xt = paddle.to_tensor(x)
        out_d = ddp(xt, )
        # activations must actually be dp-sharded (SPMD, not replicated)
        assert not out_d._data.sharding.is_fully_replicated
        loss_d = ddp.scale_loss(
            paddle.mean((out_d - paddle.to_tensor(y)) ** 2))
        loss_d.backward()
        ddp.apply_collective_grads()
        opt_ddp.step()
        opt_ddp.clear_grad()

        np.testing.assert_allclose(float(loss_r), float(loss_d), rtol=2e-5)

    for pr, pd in zip(ref.parameters(), ddp.parameters()):
        np.testing.assert_allclose(pr.numpy(), pd.numpy(), atol=2e-5)
