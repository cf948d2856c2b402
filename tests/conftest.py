"""Test harness: force an 8-device CPU mesh (SURVEY.md §4).

The JAX analogue of the reference testing its socket protocol on Spark
``local[N]``: ``--xla_force_host_platform_device_count=8`` gives eight
CPU devices in one process, so every pjit/shard_map collective path runs
for real without TPU hardware.

The platform is switched through jax.config before any backend is
initialized, so the suite runs on CPU whatever ``JAX_PLATFORMS`` says
(works as long as jax.devices() hasn't run yet).
"""

import os

os.environ["KERAS_BACKEND"] = "jax"
# LLVM's optimizer off: compiling ~5,000 toy programs is most of a cold
# run (PR 29: 29 % fewer CPU seconds cold, a warm run the same).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           + " --xla_backend_optimization_level=0")
# The WHOLE suite runs under the lock-order sanitizer (utils/locks.py):
# inversions, double-acquires and callbacks under a lock raise at the
# site, and the autouse fixture below fails any test that recorded one.
# Read when locks is imported; DKT_LOCK_SANITIZER=0 overrides.
os.environ.setdefault("DKT_LOCK_SANITIZER", "1")
# One compile cache for the checkout, where configure_compile_cache()
# (utils/misc.py) puts it for every other entry point: the workers, the
# subprocess tests and the driver's next run of the tree share it.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
# XLA:CPU logs 3 kB of error at every entry it loads ("feature
# +prefer-no-gather is not supported on the host": a preference it
# wrote itself), thousands a run, into a failing test's captured stderr.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax

if os.environ.get("DKT_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import faulthandler
import signal
import sys

import numpy as np
import pytest

# make_* re-exported for tests that import them from conftest; helpers.py
# is the home a subprocess test imports without the env mutation above.
from helpers import make_blobs, make_mlp  # noqa: F401


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 test devices, got {devs}"
    return devs


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def blobs():
    return make_blobs()


@pytest.fixture()
def mlp():
    return make_mlp()


# ---------------------------------------------------------------- markers
# The driver's gate (/root/TESTS_LAST_RUN.json, PR 28): `timeout 1470
# env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' -p
# xdist -n 6 --dist loadfile ...`: 866 of 1,071 tests.  Measured on an
# 8-core box (PR 29, CHANGES.md): 206 s and 1,252 CPU seconds cold, 123
# and 682 with a warm cache (the parent: 363 and 2,481); the driver's
# boxes are up to five times slower.  SLOW names the tests the gate
# leaves out, by file, each group under the fast tests that hold its
# contract; MULTIPROCESS tests spawn OS processes (`pytest -m
# multiprocess`) and keep their own subprocess timeouts.

MULTIPROCESS = {
    "test_checkpoint::test_sigkill_midrun_then_resume_matches_straight",
    "test_deploy::test_four_process_smoke",
    "test_deploy::test_two_process_adag_matches_single_process",
    "test_deploy::test_two_process_checkpoint_save_and_resume",
    "test_deploy::test_two_process_device_data_adag_matches_single",
    "test_deploy::test_two_process_downpour_matches_single_process",
    "test_deploy::test_two_process_eval_dataset_matches_single",
    "test_deploy::test_two_process_lm_trainer_matches_single_process",
    "test_deploy::test_two_process_model_axis_crosses_boundary",
    "test_deploy::test_two_process_packed_training_matches_single",
    "test_zoo_and_entry::test_graft_entry_multichip",
}

SLOW = MULTIPROCESS | {
    # fast: test_blockwise_window_matches_naive, test_pallas_window_interpret
    "test_attention::test_flash_attention_window_grads_fallback",
    "test_attention::test_pallas_window_backward_interpret",
    "test_attention::test_pallas_window_banded_grid_asymmetric_blocks",
    # fast: test_bench_paged_rows; test_router.py and test_disagg.py
    "test_bench_contract::test_bench_router_affinity_row",
    "test_bench_contract::test_bench_router_disagg_row",
    # fast: test_serving_paged::test_paged_zero_steady_state_compiles
    "test_budget_guards::test_compile_count_guard_passes",
    # fast: test_driver_coordinated_restart_protocol (in-process)
    "test_cluster::test_two_process_kill_one_host_coordinated_restart",
    # fast: test_disagg_parity_greedy_and_role_exclusivity
    "test_disagg::test_disagg_parity_kv_int8",
    # fast: test_packing::test_packed_eval_weighted_by_valid_counts
    "test_eval_hook::test_perplexity_evaluator_matches_trainer_eval",
    # fast: test_adag_variant_converges_to_baseline and the ADAG family
    "test_exchange::test_lm_int8ef_converges_and_is_deterministic",
    "test_exchange::test_lm_sync_every_1_and_4_converge",
    "test_exchange::test_lm_adasum_and_zero1_int8_converge",
    "test_exchange::test_lm_int8ef_checkpoint_resume",
    "test_exchange::test_lm_zero1_int8_shards_opt_memory",
    # fast: test_adag_fsdp_matches_dp, test_fsdp_plan_and_plan_conflict
    "test_fsdp::test_lm_fsdp_checkpoint_resume",
    "test_fsdp::test_lm_fsdp_composes_with_tp",
    "test_fsdp::test_lm_fsdp_matches_dp",
    "test_fsdp::test_lm_fsdp_shards_param_memory",
    # fast: test_kv_int8_decode_close_to_fp, test_generate_eos_sticky
    "test_generate::test_beam_prompt_cache_matches_full_prompt",
    "test_generate::test_beam_ancestry_equals_physical_reorder",
    "test_generate::test_prompt_cache_matches_full_prompt",
    "test_generate::test_beam_eos_freezes_score",
    "test_generate::test_beam_frozen_score_is_length_invariant",
    "test_generate::test_beam_length_penalty",
    "test_generate::test_beam_length_penalty_frozen_lengths",
    "test_generate::test_beam_prefill_matches_sequential",
    "test_generate::test_beam_scores_match_rescoring_and_beat_greedy",
    "test_generate::test_beam_search_windowed_cfg",
    "test_generate::test_beam_validation_and_quantized",
    "test_generate::test_beam_width_1_equals_greedy",
    "test_generate::test_cached_decode_matches_full_forward",
    "test_generate::test_generate_greedy_matches_argmax_rollout",
    "test_generate::test_generate_min_p_sampling",
    "test_generate::test_generate_ragged_batch_matches_individual",
    "test_generate::test_generate_rope_greedy_matches_rollout",
    "test_generate::test_generate_sampling_deterministic_per_key",
    "test_generate::test_generate_temperature_needs_key",
    "test_generate::test_generate_tiny_top_p_equals_greedy",
    "test_generate::test_generate_topk1_equals_greedy",
    "test_generate::test_gqa_cache_is_smaller_and_decode_matches",
    "test_generate::test_moe_capacity_vs_dense_divergence_bounded",
    "test_generate::test_prefill_eos_matches_sequential",
    "test_generate::test_prefill_matches_sequential_generate",
    "test_generate::test_prefill_matches_sequential_gqa",
    "test_generate::test_prefill_moe_matches_sequential",
    "test_generate::test_prefill_sampling_matches_sequential",
    "test_generate::test_quantized_decode_matches_f32_greedy",
    "test_generate::test_rolling_decode_long_prompt_sequential_fallback",
    "test_generate::test_rolling_decode_matches_large_cache",
    "test_generate::test_rolling_decode_quantized",
    "test_generate::test_rolling_decode_sampling_and_eos",
    "test_generate::test_beam_windowed_ancestry_equals_physical",
    "test_generate::test_rolling_beam_matches_large_cache",
    # fast: test_zero1::test_lm_zero1_matches_dp, test_lm_eval_validation
    "test_lm_trainer::test_ema_resume_matches_straight_run",
    "test_lm_trainer::test_lm_dropout_resume_matches_straight",
    "test_lm_trainer::test_lm_dropout_trains_and_is_reproducible",
    "test_lm_trainer::test_lm_eval_moe_excludes_aux",
    "test_lm_trainer::test_lm_eval_perplexity",
    "test_lm_trainer::test_lm_grad_accum_matches_large_batch",
    "test_lm_trainer::test_lm_grad_clip",
    "test_lm_trainer::test_lm_profile_dir_writes_trace",
    "test_lm_trainer::test_lm_trainer_accepts_optax_optimizers",
    "test_lm_trainer::test_lm_trainer_dp",
    "test_lm_trainer::test_lm_trainer_pp_ep",
    "test_lm_trainer::test_lm_trainer_pp_sp",
    "test_lm_trainer::test_lm_trainer_resume_matches_straight_run",
    "test_lm_trainer::test_lm_trainer_shuffle_deterministic",
    "test_lm_trainer::test_lm_trainer_tp_sp",
    "test_lm_trainer::test_lm_weight_decay_masks_norm_scales",
    "test_lm_trainer::test_lm_device_data_matches_streaming",
    "test_lm_trainer::test_ema_decay_matches_manual_shadow",
    # fast: test_merge_matches_manual_delta, test_optimizer_state_excludes_base
    "test_lora::test_lora_checkpoint_resume_matches_straight",
    "test_lora::test_lora_merged_serves_speculatively",
    "test_lora::test_lora_grad_accum_matches_large_batch",
    "test_lora::test_merged_model_serves",
    "test_lora::test_zero_init_merge_is_identity",
    "test_lora::test_lora_composes_with_tp_mesh_and_segments",
    "test_lora::test_finetune_trains_adapters_and_freezes_base",
    # fast: test_router::test_drain_midstream_keeps_parity_and_waterfall
    "test_obs_live::test_request_waterfall_speculative_and_unknown_id",
    # fast: test_segments_equal_separate_documents
    "test_packing::test_packed_forward_equals_separate_docs",
    "test_packing::test_packed_forward_ring_mesh_matches_default",
    "test_packing::test_packed_forward_pipeline_matches_default",
    "test_packing::test_lm_trainer_packed_ring_mesh",
    "test_packing::test_lm_trainer_packed_pipeline_mesh",
    "test_packing::test_remat_composes_with_segments",
    "test_packing::test_pallas_interpret_segments_fwd_bwd",
    "test_packing::test_lm_trainer_packed_tp_fsdp_mesh",
    "test_packing::test_packed_loss_equals_weighted_separate_losses",
    "test_packing::test_lm_trainer_packed_end_to_end",
    "test_packing::test_flash_fallback_segments_grads_match_naive",
    # fast: test_pipeline_matches_sequential, test_pipeline_gradients
    "test_pipeline::test_pipelined_moe_aux_flows_into_loss",
    "test_pipeline::test_pipelined_moe_with_seq_axis_aux_consistent",
    "test_pipeline::test_pipelined_ring_attention_matches_single",
    "test_pipeline::test_pipelined_transformer_matches_single",
    "test_pipeline::test_pipelined_transformer_trains",
    # fast: test_transformer_pipelined_remat
    "test_remat::test_remat_policy_matches_plain_remat",
    "test_remat::test_transformer_remat_matches_plain",
    # fast: test_matches_keras_sequences, test_weights_interchange_both_ways
    "test_rnn::test_matches_keras_last_state",
    "test_rnn::test_serialization_round_trip",
    "test_rnn::test_trains_under_single_trainer",
    # fast: test_warmup_cosine_through_single_trainer
    "test_schedules::test_schedule_through_lm_trainer",
    # fast: test_load_lm_decodes_eagerly_without_jit
    "test_serialization::test_save_load_lm_round_trip",
    # fast: test_rolling_engine_matches_rolling_generate
    "test_serving::test_engine_fuzz_schedule_matches_solo",
    "test_serving::test_per_request_fuzz_schedule_matches_solo",
    "test_serving::test_staggered_admission_and_lane_reuse",
    "test_serving::test_speculative_batcher_matches_solo",
    "test_serving::test_speculative_batcher_sampled_matches_solo",
    # fast: test_chunked_prefill_parity_and_interleave
    "test_serving_fastpath::test_chunked_prefill_sampled_and_tail_overlap",
    "test_serving_fastpath::test_elastic_chunked_pool_enqueue",
    "test_serving_fastpath::test_prefix_pool_sampled_kv_int8_and_lane_reuse",
    # fast: test_paged_kv_int8_exact_parity, test_paged_chunked_prefill_parity
    "test_serving_paged::test_kv_int8_prefill_engine_agreement",
    "test_serving_paged::test_paged_greedy_parity_staggered_and_lane_reuse",
    # fast: test_sharded_cb_greedy_bit_exact, test_sharded_cb_sampled_bit_exact
    "test_serving_sharded::test_sharded_paged_greedy_and_sampled_bit_exact",
    "test_serving_sharded::test_sharded_speculative_greedy_parity",
    # fast: test_generate_sampled_tp_sharded_matches_single
    "test_sharded_decode::test_speculative_tp_sharded_matches_single",
    "test_sharded_decode::test_beam_search_fsdp_scattered_matches_single",
    "test_sharded_decode::test_beam_search_tp_sharded_matches_single",
    "test_sharded_decode::test_generate_greedy_fsdp_scattered_matches_single",
    "test_sharded_decode::test_generate_greedy_tp_sharded_matches_single",
    "test_sharded_decode::test_beam_prompt_cache_under_tp",
    # fast: test_windowed_greedy_matches_generate, test_jittable
    "test_speculative::test_decode_chunk_matches_decode_step",
    "test_speculative::test_eos_matches_generate",
    "test_speculative::test_eos_stops_rows_early",
    "test_speculative::test_decode_chunk_per_row_offsets",
    "test_speculative::test_greedy_matches_generate",
    "test_speculative::test_greedy_rope_gqa_matches_generate",
    "test_speculative::test_greedy_moe_matches_generate",
    "test_speculative::test_nonuniform_acceptance_rows_finish_cleanly",
    "test_speculative::test_perfect_draft_accepts_everything",
    "test_speculative::test_quantized_target_matches_quantized_generate",
    "test_speculative::test_sampled_matches_target_distribution",
    "test_speculative::test_sampled_deterministic_per_key",
    "test_speculative::test_windowed_small_ring_matches_big_cache_sampled",
    # fast: test_encode_corpus_packs_lm_rows
    "test_tokenizer::test_tokenizer_feeds_lm_trainer",
    # fast: test_forward_shape_and_determinism, test_rope_ring_matches_single
    "test_transformer::test_attention_window_composes_with_moe",
    "test_transformer::test_attention_window_lm_trainer_ring",
    "test_transformer::test_attention_window_matches_manual_mask",
    "test_transformer::test_attention_window_trains",
    "test_transformer::test_chunked_ce_handles_nondivisible_token_count",
    "test_transformer::test_chunked_ce_loss_and_grads_match_full",
    "test_transformer::test_chunked_ce_pipelined_trains_via_lm_trainer",
    "test_transformer::test_chunked_ce_trains",
    "test_transformer::test_dropout_deterministic_per_key_and_off_without_rng",
    "test_transformer::test_dropout_training_learns",
    "test_transformer::test_expert_parallel_matches_single",
    "test_transformer::test_gqa_shapes_and_learning",
    "test_transformer::test_moe_train_step_learns",
    "test_transformer::test_rope_forward_and_learning",
    "test_transformer::test_rope_params_have_no_pos_table",
    "test_transformer::test_rope_trains_past_max_len",
    "test_transformer::test_train_step_learns_copy_task",
    "test_transformer::test_z_loss_chunked_matches_full",
    "test_transformer::test_z_loss_trains_and_shrinks_normalizer",
    # fast: test_lm_zero1_matches_dp, test_lm_zero1_shards_opt_memory
    "test_zero1::test_lm_zero1_checkpoint_resume",
    "test_zero1::test_lm_zero1_clip_ema_matches_dp",
    "test_zero1::test_lm_zero1_grad_accum_matches_dp",
    # fast: test_lm_zero2_matches_dp, test_lm_zero3_matches_dp
    "test_zero_stages::test_lm_zero3_checkpoint_resume",
    "test_zero_stages::test_lm_zero3_grad_accum_matches_dp",
    "test_zero_stages::test_lm_zero3_clip_ema_matches_dp",
    "test_zero_stages::test_lm_zero3_device_data_matches_streaming",
    "test_zero_stages::test_lm_zero3_eval_matches_dp",
    # fast: test_graft_entry_multichip_raises_without_the_devices
    "test_zoo_and_entry::test_cifar_cnn_forward",
    "test_zoo_and_entry::test_graft_entry_single",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        key = f"{item.module.__name__}::{item.originalname}"
        if key in SLOW:
            item.add_marker(pytest.mark.slow)
        if key in MULTIPROCESS:
            item.add_marker(pytest.mark.multiprocess)


# A limit of its own for each test, so that a hang on some box fails
# ONE named test with every thread's stack and not the run: five times
# the slowest set-up measured (55.7 s).  A hang inside native code
# cannot be interrupted from Python: the handler then runs when the
# call returns, and until then the driver's clock is the limit.
_TEST_LIMIT_S = 300


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    if item.get_closest_marker("multiprocess"):
        return (yield)

    def over(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid} ran over its {_TEST_LIMIT_S} s "
                    "(every thread's stack: captured stderr)", pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


# Every live XLA-CPU executable holds dozens to hundreds of LLVM-JIT
# mmap sections and jax's caches keep every program alive: a serial
# full run hit vm.max_map_count (~65k) at ~85% and died in
# backend_compile (docs/xla_cpu_compile_crash.md).  Dropping the caches
# every 50 tests holds the map count flat, at the price of recompiles.
# An xdist worker runs a sixth of the suite and never gets near the
# wall: it keeps its caches.
_TESTS_PER_CACHE_DROP = 50
_test_tally = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_llvm_jit_maps():
    yield
    if "PYTEST_XDIST_WORKER" in os.environ:
        return
    _test_tally["n"] += 1
    if _test_tally["n"] % _TESTS_PER_CACHE_DROP == 0:
        jax.clear_caches()


# ------------------------------------------------ concurrency gate
# Two autouse fixtures make thread discipline a property of EVERY test:
#
# - _lock_sanitizer_gate: a lock-order violation the sanitizer recorded
#   during the test fails it, even when the raising thread swallowed
#   the exception (SLO ticker, HTTP handlers).  tests/test_locks.py's
#   positives opt out with @pytest.mark.expected_lock_violations.
# - _no_thread_leaks: a test must not leave its own dkt-named threads
#   running (a leaked dkt-telemetry thread holds the port for the next
#   test).  A gc pass first lets abandoned Prefetcher/engine objects
#   run their __del__, then stragglers get a short grace.  Opt out
#   with @pytest.mark.bg_threads (e.g. a deliberately hung probe).

def _locks_module():
    return sys.modules.get("distkeras_tpu.utils.locks")


@pytest.fixture(autouse=True)
def _lock_sanitizer_gate(request):
    locks = _locks_module()
    before = locks.violation_count() if locks is not None else 0
    yield
    if request.node.get_closest_marker("expected_lock_violations"):
        return
    locks = _locks_module()
    if locks is None:
        return
    new = locks.violations()[before:]
    assert not new, (
        "the lock sanitizer recorded violation(s) during this test:\n"
        + "\n".join(v.format() for v in new))


@pytest.fixture(autouse=True)
def _no_thread_leaks(request):
    import threading as _threading

    before = set(_threading.enumerate())
    yield
    if request.node.get_closest_marker("bg_threads"):
        return

    def leaked():
        return [t for t in _threading.enumerate()
                if t.is_alive() and t not in before
                and t.name.startswith("dkt-")]

    left = leaked()
    if left:
        import gc
        import time as _time

        gc.collect()   # abandoned Prefetcher/session: __del__ stops it
        deadline = _time.monotonic() + 2.0
        while leaked() and _time.monotonic() < deadline:
            _time.sleep(0.05)
        left = leaked()
    assert not left, (
        f"test leaked live background thread(s): "
        f"{sorted(t.name for t in left)} — stop/close them, or mark "
        "the test @pytest.mark.bg_threads if the background work is "
        "intentional")
