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
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# Run the WHOLE tier-1 suite under the lock-order sanitizer
# (utils/locks.py): every TracedLock/TracedRLock the production code
# constructs is instrumented, lock-order inversions / double-acquires
# / callbacks-under-lock raise at the offending site, and the autouse
# fixture below fails any test that recorded a violation.  Set before
# anything imports distkeras_tpu (the env is read at locks import);
# the driver can override with DKT_LOCK_SANITIZER=0.
os.environ.setdefault("DKT_LOCK_SANITIZER", "1")

import jax

if os.environ.get("DKT_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# Re-exported so tests keep importing them from conftest; helpers.py is
# the conftest-free home (subprocess tests import it without triggering
# the env mutation above).
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
# Suite gating (SURVEY.md §4 "do better, cheaply"): `pytest -m "not
# slow"` is the fast gate (~4-5 min on one CPU core, >= 1 test per
# subsystem); the full suite (~25 min) stays the merge gate.  The SLOW
# set was measured with `pytest --durations=0` (call time >= 4 s on one
# core); refresh it the same way when tests move.  Deliberate
# exceptions when refreshing: test_sharded_decode::
# test_generate_sampled_tp_sharded_matches_single stays UNmarked even
# though it exceeds the threshold — it is the fast gate's one
# sharded-decode representative (the README promises the gate covers
# every subsystem) — and test_zero1::test_adag_zero1_matches_replicated
# / test_zero1::test_lm_zero1_matches_dp stay UNmarked as the fast
# gate's ZeRO-1 parity representatives for the two trainer families
# (the sharded-update acceptance contract).  MULTIPROCESS tests
# spawn OS subprocesses (multi-host runtime, crash recovery, the driver
# dryrun) — they are also slow, and worth selecting on their own when
# debugging the distributed runtime: `pytest -m multiprocess`.

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
    "test_serving::test_engine_fuzz_schedule_matches_solo",
    "test_serving::test_per_request_fuzz_schedule_matches_solo",
    "test_serving::test_staggered_admission_and_lane_reuse",
    "test_generate::test_beam_prompt_cache_matches_full_prompt",
    "test_generate::test_beam_ancestry_equals_physical_reorder",
    "test_generate::test_prompt_cache_matches_full_prompt",
    "test_lm_trainer::test_ema_resume_matches_straight_run",
    "test_lora::test_lora_checkpoint_resume_matches_straight",
    "test_lora::test_lora_merged_serves_speculatively",
    "test_lora::test_lora_grad_accum_matches_large_batch",
    "test_lora::test_merged_model_serves",
    "test_lora::test_zero_init_merge_is_identity",
    "test_lora::test_lora_composes_with_tp_mesh_and_segments",
    "test_lora::test_finetune_trains_adapters_and_freezes_base",
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
    "test_sharded_decode::test_speculative_tp_sharded_matches_single",
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
    "test_attention::test_flash_attention_window_grads_fallback",
    "test_attention::test_pallas_window_backward_interpret",
    "test_attention::test_pallas_window_banded_grid_asymmetric_blocks",
    "test_eval_hook::test_perplexity_evaluator_matches_trainer_eval",
    "test_fsdp::test_lm_fsdp_checkpoint_resume",
    "test_fsdp::test_lm_fsdp_composes_with_tp",
    "test_fsdp::test_lm_fsdp_matches_dp",
    "test_fsdp::test_lm_fsdp_shards_param_memory",
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
    "test_pipeline::test_pipelined_moe_aux_flows_into_loss",
    "test_pipeline::test_pipelined_moe_with_seq_axis_aux_consistent",
    "test_pipeline::test_pipelined_ring_attention_matches_single",
    "test_pipeline::test_pipelined_transformer_matches_single",
    "test_pipeline::test_pipelined_transformer_trains",
    "test_remat::test_remat_policy_matches_plain_remat",
    "test_remat::test_transformer_remat_matches_plain",
    "test_rnn::test_matches_keras_last_state",
    "test_rnn::test_serialization_round_trip",
    "test_rnn::test_trains_under_single_trainer",
    "test_schedules::test_schedule_through_lm_trainer",
    "test_serialization::test_save_load_lm_round_trip",
    "test_sharded_decode::test_beam_search_fsdp_scattered_matches_single",
    "test_sharded_decode::test_beam_search_tp_sharded_matches_single",
    "test_sharded_decode::test_generate_greedy_fsdp_scattered_matches_single",
    "test_sharded_decode::test_generate_greedy_tp_sharded_matches_single",
    "test_tokenizer::test_tokenizer_feeds_lm_trainer",
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
    "test_zoo_and_entry::test_cifar_cnn_forward",
    "test_zoo_and_entry::test_graft_entry_single",
    "test_zero1::test_lm_zero1_checkpoint_resume",
    "test_zero1::test_lm_zero1_clip_ema_matches_dp",
    "test_zero1::test_lm_zero1_grad_accum_matches_dp",
    # Exchange-layer LM legs: the fast gate keeps the ADAG family's
    # full variant matrix (convergence, determinism, residual
    # diagnostics, pickle checkpoint resume, Supervisor bit-for-bit);
    # the LM spellings — same merge rules on the bigger model, whose
    # ~21-program compiles dominate wall time — run in the merge gate.
    "test_exchange::test_lm_int8ef_converges_and_is_deterministic",
    "test_exchange::test_lm_sync_every_1_and_4_converge",
    "test_exchange::test_lm_adasum_and_zero1_int8_converge",
    "test_exchange::test_lm_int8ef_checkpoint_resume",
    "test_exchange::test_lm_zero1_int8_shards_opt_memory",
    # The 2-process coordinated-restart smoke joins its full-ladder
    # sibling in the merge gate: the fast gate keeps every in-process
    # cluster protocol test (driver restart protocol, flap ladder,
    # watchdog, torn-checkpoint selection), and the tier-1 wall-clock
    # budget goes to the exchange-layer matrix instead of a second
    # spawned-subprocess collective run.
    "test_cluster::test_two_process_kill_one_host_coordinated_restart",
    # Round-11 fast-gate rebalance: the round-10 serving fast path
    # grew the gate past its wall clock (measured 1029 s against the
    # 870 s tier-1 budget on the 8-CPU harness, before this round
    # added anything), so the heaviest SECOND spellings of already-
    # fast-covered contracts move to the merge gate.  What stays fast
    # per subsystem: beam — width-1/scores/eos/prefill/length-penalty/
    # ancestry + the kv_int8 rolling-beam parity; speculative — the
    # whole solo-fn matrix, the rolling batcher parity + draft-fault
    # chaos tests, and the pooled engine parity; chunked prefill —
    # greedy parity + the 1k-prompt interleave bound; device_data —
    # the ADAG family matrix (test_device_data.py); TP decode — the
    # prompt-cache decode test; compile counts — the graph-lint CLI
    # and in-process census/parity stay, the full recorded-session
    # guard subprocess (61 s) runs at merge (and in this round's
    # obs_live work the new session asserts its zero-compile claim
    # in-session, so a regression still fails the guard itself).
    "test_budget_guards::test_compile_count_guard_passes",
    "test_lm_trainer::test_lm_device_data_matches_streaming",
    "test_lm_trainer::test_ema_decay_matches_manual_shadow",
    "test_generate::test_beam_windowed_ancestry_equals_physical",
    "test_generate::test_rolling_beam_matches_large_cache",
    "test_serving::test_speculative_batcher_matches_solo",
    "test_serving::test_speculative_batcher_sampled_matches_solo",
    "test_serving_fastpath::test_chunked_prefill_sampled_and_tail_overlap",
    "test_serving_fastpath::test_elastic_chunked_pool_enqueue",
    "test_sharded_decode::test_beam_prompt_cache_under_tp",
    "test_speculative::test_windowed_small_ring_matches_big_cache_sampled",
    "test_obs_live::test_request_waterfall_speculative_and_unknown_id",
    # Round-12 (ZeRO-2/3): the fast gate keeps one parity test per
    # stage per family (ADAG zero2+zero3, LM zero2+zero3), the
    # per-device-bytes acceptance assertions, the Supervisor
    # bit-for-bit chaos leg (MLP-fast) and the codec-rules exchange;
    # the heavier SECOND spellings of already-covered contracts — the
    # stage-3 checkpoint round-trips (both backends), the
    # clip+EMA/grad_accum/device_data/eval stage-3 variants — run in
    # the merge gate to hold the tier-1 wall clock (the ISSUE's
    # declared escape hatch for exactly these legs).
    "test_zero_stages::test_lm_zero3_checkpoint_resume",
    "test_zero_stages::test_lm_zero3_grad_accum_matches_dp",
    "test_zero_stages::test_lm_zero3_clip_ema_matches_dp",
    "test_zero_stages::test_lm_zero3_device_data_matches_streaming",
    "test_zero_stages::test_lm_zero3_eval_matches_dp",
    # Round-20 rebalance (contract-lint gate): the gate itself is
    # pure-AST and cheap (~5 s for tests/test_contract_lint.py +
    # the schema-equality guard), but the suite had crept to 896 s
    # measured against the 870 s tier-1 wall, so the heaviest SECOND
    # spellings of already-fast-covered contracts move to the merge
    # gate.  What stays fast per subsystem: sharded serving — the
    # residency-digest sharded-vs-solo parity, elastic-cb scaling,
    # FSDP-plan serving, router-over-sharded-replica, prefix-pool and
    # cb-sampled bit-exact legs; paged serving — chunked-prefill /
    # sampled-per-request / CoW-fork / stem-sharing / admission-
    # tolerance parities; disagg — greedy+role-exclusivity, seeded
    # sampling, chunked prefill, export/import refcounts, cross-hop
    # streaming, prefill-failure fallback; prefix pool — the engine
    # parity + zero-prefix-work and speculative-pool greedy legs;
    # bench contract — the paged and load/elastic/spec rows.  The
    # moved tests re-spell those same contracts on a second axis
    # (kv_int8 x prefill-agreement, sampled x sharded-paged,
    # speculative x sharded, staggered-lane x paged, bench rows whose
    # underlying router/disagg paths have dedicated fast tests) and
    # run in the full merge suite.
    "test_serving_sharded::test_sharded_paged_greedy_and_sampled_bit_exact",
    "test_serving_sharded::test_sharded_speculative_greedy_parity",
    "test_serving_paged::test_kv_int8_prefill_engine_agreement",
    "test_serving_paged::test_paged_greedy_parity_staggered_and_lane_reuse",
    "test_serving_fastpath::test_prefix_pool_sampled_kv_int8_and_lane_reuse",
    "test_disagg::test_disagg_parity_kv_int8",
    "test_bench_contract::test_bench_router_affinity_row",
    "test_bench_contract::test_bench_router_disagg_row",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        key = f"{item.module.__name__}::{item.originalname}"
        if key in SLOW:
            item.add_marker(pytest.mark.slow)
        if key in MULTIPROCESS:
            item.add_marker(pytest.mark.multiprocess)


# Every live XLA-CPU executable holds dozens-to-hundreds of LLVM-JIT
# mmap sections, and jax's global caches keep every test's programs
# alive for the whole run — a serial full run used to hit the kernel's
# vm.max_map_count wall (~65k) at ~85% and SIGSEGV inside
# backend_compile (root cause + repro: docs/xla_cpu_compile_crash.md).
# Dropping the caches every 50 tests releases the maps (measured: map
# count pinned flat vs linear growth to the wall) at the price of
# recompiles across the boundary.  The xdist gate (-n 4) never gets
# near the wall; this makes plain serial runs safe too.
_TESTS_PER_CACHE_DROP = 50
_test_tally = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_llvm_jit_maps():
    yield
    _test_tally["n"] += 1
    if _test_tally["n"] % _TESTS_PER_CACHE_DROP == 0:
        jax.clear_caches()


# ------------------------------------------------ concurrency gate
# (round 12)  Two autouse fixtures make thread discipline a tier-1
# property of EVERY test, not just the ones that think about threads:
#
# - _lock_sanitizer_gate: any lock-order violation the runtime
#   sanitizer recorded during the test fails it — even when the
#   raising thread swallowed the exception (SLO ticker, HTTP handler
#   threads catch broadly).  Tests that deliberately provoke
#   violations (tests/test_locks.py positives) opt out with
#   @pytest.mark.expected_lock_violations.
# - _no_thread_leaks: a test must not leave its own background
#   threads running (the PR-8 EADDRINUSE class: a leaked
#   dkt-telemetry thread holds the port for the next test).  All
#   subsystem threads are dkt-named; a gc pass first lets abandoned
#   Prefetcher/engine objects run their __del__ cleanup, then
#   stragglers get a short grace to finish stopping.  Opt out with
#   @pytest.mark.bg_threads for tests that intentionally leave
#   background work (e.g. a deliberately hung device probe).

import sys as _sys


def _locks_module():
    return _sys.modules.get("distkeras_tpu.utils.locks")


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
