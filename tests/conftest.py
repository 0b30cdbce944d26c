"""Test harness: emulated 8-device CPU mesh.

The reference tests distributed code by spawning ≤4 NCCL processes per
node (apex/transformer/testing/distributed_test_base.py:22-74).  The
TPU-native equivalent runs every test in ONE process against an 8-way
virtual CPU mesh via XLA's host-platform device-count flag — collectives
and shardings compile and execute exactly as on an 8-chip slice.
"""

import os

# The tests never touch an accelerator, whatever the machine holds.
# The environment carries the choice to the child processes tests
# start (probe scripts, examples, the multiproc launcher); this
# process pins it through jax.config before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", False)
assert jax.device_count() == 8, jax.devices()

# Smoke tier (≡ the reference's per-directory L0 subsets,
# tests/L0/run_test.py:19-34): ONE fast, meaningful test per subsystem,
# ~90 s serial on the virtual mesh.  `pytest -m smoke`.  The full suite
# (`pytest tests/`) is the L1 equivalent — ~30 min serial, documented in
# README.  Keep every entry under ~10 s; timings from --durations=0.
SMOKE = {
    # kernels
    "test_flash_attention.py::test_flash_grads[True]",
    "test_softmax.py::test_scaled_masked_softmax",
    "test_layer_norm.py::test_layer_norm_grads[True-shape0]",
    "test_xentropy.py::test_xent_grad[0.0]",
    "test_fused_dense_mlp.py::test_linear_gelu_linear",
    # optimizers
    "test_optimizers.py::test_fused_adam_vs_optax_adamw[0.0]",
    "test_distributed_optimizers.py::test_dist_adam_matches_fused_adam",
    # data parallel / amp
    "test_ddp.py::test_make_train_step_matches_full_batch",
    "test_ddp.py::test_make_train_step_with_amp_dynamic_scaling",
    "test_distributed_tier.py::TestSyncBNDistributed::"
    "test_syncbn_matches_global_bn",
    # model parallel
    "test_tensor_parallel_layers.py::test_column_parallel_linear",
    "test_mesh_collectives.py::test_copy_reduce_pair",
    "test_pipeline_parallel.py::test_pipeline_matches_sequential[4]",
    "test_schedules_common.py::TestSchedulesCommon::"
    "test_backward_step_chain_matches_full_grad",
    # long context
    "test_context_parallel.py::test_ring_attention_matches_dense[False]",
    # models end-to-end
    "test_gpt_minimal.py::test_gpt_trains_tp_dp",
    "test_bert_minimal.py::test_bert_trains_with_lamb",
    # contrib
    "test_contrib_ops.py::test_self_multihead_attn[False]",
    "test_contrib_ops.py::test_transducer_joint",
    "test_contrib_spatial.py::test_spatial_conv_matches_dense",
    "test_misc_components.py::test_rnn_cells[LSTM]",
    # aux subsystems
    "test_checkpoint.py::test_checkpoint_roundtrip",
    "test_host_runtime.py::test_flat_layout",
}


# L1 tier (≡ the reference's tests/L1 heavy suites): the measured-slow
# tests (≥14 s serial; durations from a full --durations run) that push
# the default run past the budget.  Most files keep lighter siblings in
# the default (L0) tier (the cross-product file is l1 wholesale; its
# default-tier coverage lives in test_amp_casts.py + the e2e model
# tests); `pytest -m l1` runs these.
L1 = {
    "test_context_parallel.py::test_ring_attention_128k_causal_fwd_bwd",
    "test_distributed_optimizers.py::"
    "test_dist_adam_100m_scale_and_state_roundtrip",
    "test_distributed_optimizers.py::test_dist_lamb_100m_scale",
    "test_examples.py::test_dcgan_runs[O1]",
    "test_examples.py::test_dcgan_runs[O2]",
    "test_examples.py::test_simple_distributed_runs",
    "test_examples.py::test_long_context_training_runs",
    "test_bert_minimal.py::test_bert_loss_consistent_across_tp",
    "test_bert_minimal.py::test_bert_flash_vs_dense_attention_parity",
    "test_bert_minimal.py::test_bert_pad_mask",
    # (all of test_l1_cross_product.py is l1 via its module-level
    # pytestmark — round 5 moved the parity half there too, restoring
    # the default tier's runtime margin)
    "test_gpt_pipelined.py::test_pipelined_matches_plain",
    "test_gpt_pipelined.py::test_pipelined_interleaved_matches",
    "test_gpt_pipelined.py::test_pipelined_grads_flow",
    "test_gpt_pipelined.py::"
    "test_pipelined_training_keeps_tied_embed_in_sync",
    "test_resnet_e2e.py::test_opt_level_parity",
    "test_resnet_e2e.py::test_resnet_trains[O0]",
    "test_resnet_e2e.py::test_resnet_trains[O1]",
    "test_optimizers.py::test_master_dtype_bf16_trains",
    "test_gpt_minimal.py::test_sequence_parallel_matches",
    "test_gpt_minimal.py::test_loss_consistent_across_tp",
    "test_gpt_minimal.py::test_init_loss_near_uniform",
    "test_gpt_minimal.py::test_train_step_cache_keys_on_shapes",
    "test_sync_batchnorm.py::test_syncbn_backward_matches_full_batch",
    "test_sync_batchnorm.py::test_syncbn_matches_full_batch",
    "test_tensor_parallel_layers.py::test_vocab_parallel_cross_entropy",
    "test_tensor_parallel_layers.py::test_column_row_mlp_pattern",
    "test_tensor_parallel_layers.py::test_sequence_parallel_mlp",
    "test_tensor_parallel_layers.py::test_vocab_parallel_embedding",
    "test_misc_components.py::"
    "test_permutation_search_subdivides_wide_matrices",
    "test_gpt_pipelined.py::test_pipelined_microbatch_count_invariance",
    "test_contrib_ops.py::test_transducer_loss_grad_finite",
    "test_contrib_ops.py::test_encdec_multihead_attn",
    "test_pipeline_parallel.py::test_pipeline_grads_match_sequential",
    "test_contrib_spatial.py::test_conv_bias_relu_and_fmha",
    "test_contrib_spatial.py::test_spatial_conv_grads",
    "test_contrib_spatial.py::test_groupbn_subgroup",
    "test_distributed_tier.py::"
    "TestDDPAnalyticGrads::test_bucketed_matches_plain",
    "test_flash_attention.py::test_flash_in_kernel_dropout_mask_consistency",
    "test_fused_dense_mlp.py::test_mlp_vs_sequential",
    "test_softmax.py::test_scaled_softmax[1.0-shape0]",
}

assert not (SMOKE & L1), "a test cannot be both smoke and l1"


def pytest_collection_modifyitems(config, items):
    matched = set()
    matched_l1 = set()
    for item in items:
        key = item.nodeid.rsplit("tests/", 1)[-1]
        if key in SMOKE:
            matched.add(key)
            item.add_marker(pytest.mark.smoke)
        if key in L1:
            matched_l1.add(key)
            item.add_marker(pytest.mark.l1)
    missing = (SMOKE - matched) | (L1 - matched_l1)
    # fail loudly when a rename/reparametrize silently drops a smoke/l1
    # entry — but only when the whole suite was collected (a -k/-m or
    # path-restricted run legitimately sees a subset; the addopts
    # default of -m "not l1" deselects AFTER collection, so every item
    # is still visible here)
    unrestricted = (
        not config.getoption("keyword", default="")
        and config.getoption("markexpr", default="") in ("", "not l1")
        and not config.getoption("ignore", default=None)
        and not config.getoption("ignore_glob", default=None)
        and not config.getoption("deselect", default=None)
        and all(
            os.path.realpath(a) in (
                str(config.rootpath),
                str(config.rootpath / "tests"))
            for a in config.args))
    if missing and unrestricted:
        raise pytest.UsageError(
            f"SMOKE/L1 entries match no collected test: {sorted(missing)}")


@pytest.fixture(autouse=True)
def _fresh_mesh_state():
    yield
    from apex_tpu.parallel import mesh
    mesh.destroy_model_parallel()
