"""Test config: run everything on a virtual 8-device CPU mesh
(SURVEY.md §4 implication (c): multi-device tests without hardware via
xla_force_host_platform_device_count)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force CPU even on a machine with a chip
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags +
                               " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# a chip belongs to one process: pin the platform in jax's own config too,
# before any backend is initialized, so no test worker ever takes it.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture()
def cpu8_env():
    """Subprocess environment for mesh/probe tests: a CPU-pinned copy of
    os.environ with the 8-virtual-device XLA flag set — the ONE place the
    `xla_force_host_platform_device_count` incantation lives for tests
    (probes/bench previously each hand-rolled it).  Subprocess-isolated:
    mutating the returned dict never touches this process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env_flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in env_flags:
        env["XLA_FLAGS"] = (env_flags +
                            " --xla_force_host_platform_device_count=8"
                            ).strip()
    return env


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    from paddle_tpu.core import op as _core_op
    np.random.seed(0)
    paddle.seed(0)
    # fresh dispatch cache per test: a cached entry bakes module state read
    # at trace time, so monkeypatched kernels/flags from one test must not
    # leak compiled executables into the next (within-test caching keeps
    # the eager fast path exercised)
    _core_op.dispatch_cache_clear()
    # a test that sets another default generator (the benchmark's scripts
    # set the chip's, `rbg`) hands the next test of its worker the one it
    # found: weights drawn from a seed differ between the two
    prng = jax.config.jax_default_prng_impl
    # the benchmark's prove scripts plant a fault by replacing a function of
    # one of these modules (`prove_streamed.main` sets it for its process
    # and leaves it): put back whatever a test replaced, so that the next
    # test of its worker runs the program as it is
    import sys
    planted = {m: dict(vars(sys.modules[m])) for m in _PLANTABLE
               if m in sys.modules}
    yield
    jax.config.update("jax_default_prng_impl", prng)
    for name, before in planted.items():
        module = sys.modules[name]
        for key, value in before.items():
            if getattr(module, key, None) is not value:
                setattr(module, key, value)


_PLANTABLE = ("benchmark.generators.flood_streamed",
              "paddle_tpu.models.deepseek_v3", "paddle_tpu.models.evabyte",
              "paddle_tpu.models.nemotron_h")


# Tests measured >= ~8s on the 1-core bench host (dominated by shard_map /
# big-model XLA compiles and multi-process IO).  Centralized here so the fast
# tier (`pytest -m "not slow"`) stays under 5 minutes single-core; the full
# suite remains the green-ness bar.
_SLOW = {
    "test_vgg_and_mobilenet_forward", "test_ptq_lenet_within_one_percent",
    "test_ring_attention_matches_naive",
    "test_varlen_bert_trains_with_masked_flash_attention",
    "test_resnet_train_step", "test_mp_dataloader_correct_and_ordered",
    "test_kill_resume_with_dropout_rng",
    "test_mp_dataloader_no_shm_leak_on_early_break", "test_resnet_forward",
    "test_run_steps_matches_per_call_steps",
    "test_gradient_merge_matches_large_batch",
    "test_dropout_statistics_and_determinism",
    "test_expert_parallel_step_matches_single_device",
    "test_bert_train_step_loss_decreases", "test_kill_resume_bit_exact",
    "test_sharded_step_matches_single_device",
    "test_full_routing_matches_dense_mixture",
    "test_pipeline_parallel_matches_single_device",
    "test_pipeline_1f1b_matches_gpipe_grads", "test_moe_grad_numeric",
    "test_qat_trains_and_tracks_fp32_accuracy", "test_gpt_forward_and_train",
    "test_recompute_matches", "test_pipeline_1f1b_matches_single_device",
    "test_mp_dataloader_parallel_speedup",
    "test_gpt_kv_cache_decode_matches_full", "test_aux_loss_uniform_is_one",
    "test_mp_dataloader_concurrent_iterators",
    "test_spawn_multiprocess_smoke", "test_model_fit_eval_predict",
    "test_qat_save_quantized_model_roundtrip",
    "test_mp_dataloader_early_break_then_new_epoch_no_stale_batches",
    "test_capacity_drops_no_nan", "test_pipeline_respects_frozen_params",
    "test_lr_scheduler_state_survives_resume", "test_rnn_layers",
    "test_transformer_full", "test_allreduce_prod_signs_and_zeros",
    "test_qat_per_tensor_weight_quant_option",
    "test_sequence_concat_and_enumerate_and_expand",
    # round-3 additions over ~5s (grad sweeps / scan-compile heavy)
    "test_yolo_loss_grad_flows", "test_generate_greedy_matches_eager_argmax",
    "test_generate_all_finished_early_exit_parity",
    "test_generate_beam_matches_numpy_oracle",
    "test_deform_conv2d_grads_numeric", "test_bert_forward_shapes",
    "test_generate_topk1_matches_greedy_and_seeded_sampling_reproducible",
    "test_beam_decoder_dynamic_decode_gru",
    "test_yolo_loss_matches_numpy_reference", "test_model_summary",
    "test_fleet_facade",
    "test_train_step_sparse_first_step_matches_dense_and_learns",
    "test_data_parallel_wrapper", "test_collectives_under_shard_map",
    "test_callbacks_early_stopping", "test_adamw_rmsprop_etc_run",
    "test_data_parallel_eager_reducer_parity",
    "test_generate_eos_padding_and_score", "test_gpt_causal",
    "test_gpt_chunked_decode_matches_full", "test_standalone_c_binary",
    "test_standalone_c_train_binary", "test_train_session_python_side",
    "test_crf_trains_to_recover_transitions",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in _SLOW:
            item.add_marker(pytest.mark.slow)
