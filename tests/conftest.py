"""Test harness: the CPU backend with 8 virtual devices, so that
sharding/collective tests run without TPU hardware (SURVEY.md §4: the
reference simulates multi-device with N local processes; we simulate with
N virtual XLA host devices). JAX_PLATFORMS=cpu is set before jax is
imported and the installed JAX obeys it. The persistent compile cache is
placed by the package's own rule (paddle_tpu/__init__.py): the tests set
no directory and no threshold of their own.
"""
import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


# ---------------------------------------------------------------------------
# shard markers: one marker per file so CI (and humans) can split the
# suite — `pytest -m distributed`, `pytest -m "not kernels"`, or run
# shards in parallel processes (`pytest -n 4`, pytest-xdist).
# ---------------------------------------------------------------------------
_SHARDS = {
    "kernels": {"test_pallas_train.py", "test_long_context.py"},
    "distributed": {"test_distributed.py", "test_pipeline.py",
                    "test_moe.py", "test_multiprocess.py",
                    "test_launch.py", "test_trainer.py",
                    "test_fleet.py"},
    "surface": {"test_ops.py", "test_tensor.py", "test_api_surface.py",
                "test_functional_extra.py", "test_guards.py"},
}

# ---------------------------------------------------------------------------
# slow marks: the canonical tier-1 command runs `-m 'not slow'` under a
# 870s timeout, and the full suite takes ~25+ min on the 2-core CI box.
# The heaviest tests (from `pytest --durations`) are marked slow HERE —
# one central list, matched by nodeid substring — while every subsystem
# keeps a fast smoke in the default run (e.g. alexnet/shufflenet for
# the vision zoo, matches_full[2-False] for ring attention, the dtype
# family for the fuzz harness, flash_grad_parity_interpret for the
# Pallas flash path). Run everything with plain `pytest tests/` + no
# marker filter.
# ---------------------------------------------------------------------------
_SLOW_TESTS = (
    # vision zoo (heaviest: deep stacks compiled per test)
    "test_vision_models.py::TestVisionZoo::test_densenet121",
    "test_vision_models.py::TestVisionZoo::test_inception_v3",
    "test_vision_models.py::TestVisionZoo::test_train_step_mobilenet",
    "test_vision_models.py::TestVisionZoo::test_mobilenet_v3",
    "test_vision_models.py::TestVisionZoo::test_googlenet_aux_heads",
    "test_vision_models.py::TestVisionZoo::test_mobilenet_v1",
    "test_vision_models.py::TestVisionZoo::test_squeezenet",
    # ring attention / context parallel (smoke: matches_full, zigzag)
    "test_long_context.py::test_ring_attention_tensor_api_with_tape",
    "test_long_context.py::test_ring_attention_grads_match",
    "test_long_context.py::TestVarlenContextParallel::"
    "test_ring_varlen_parity",
    # fuzz families (smoke: the dtype family + remaining small ones)
    "test_fuzz_smoke.py::test_fuzz_family_smoke[grads",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[ops",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[rnn_dist",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[index",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[cf_fft_linalg",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[vision",
    # pipeline parallel parity (smoke: the remaining schedule tests)
    "test_pipeline.py::test_pipeline_with_grad_scaler_parity",
    "test_pipeline.py::test_llama_pipe_parity_with_monolithic",
    "test_pipeline.py::test_pipeline_spmd_grad_matches_sequential",
    "test_pipeline.py::test_pipeline_opt_state_seeding_resume",
    "test_pipeline.py::test_interleaved_virtual_stages_loss_parity",
    # Pallas flash kernels (smoke: flash_grad_parity_interpret)
    "test_pallas_train.py::test_flash_gqa_native_matches_repeated",
    "test_pallas_train.py::test_flash_bwd_pallas_kernels_direct",
    "test_pallas_train.py::test_flash_nonmultiple_seq_parity",
    "test_pallas_train.py::test_flash_varlen_kv_lens",
    # misc heavy parity tests (each file keeps faster siblings)
    "test_generation.py::TestSpeculativeDecoding::"
    "test_exact_greedy_parity_and_fewer_calls",
    "test_optimizer.py::TestTrainCurveParityVsTorch::test_curves_match",
    "test_optimizer.py::TestOptimizers::test_converges_on_quadratic["
    "Lamb",
    "test_diffusion.py::TestUNet::test_forward_shape_and_grads",
    "test_diffusion.py::TestUNet::test_train_loss_decreases",
    "test_hf_parity.py::TestLlamaHFParity::test_logits_match",
    "test_hf_parity.py::TestLlamaHFParity::"
    "test_loss_and_grad_finite_after_import",
    "test_moe.py::test_scatter_vs_dense_dispatch_parity",
    "test_pp_memory.py::test_pipeline_table",
    "test_models_nlp.py::TestBertHeads::test_mlm_trains",
    # third tier (PR 13: the canonical window tightened back to ~835s
    # body + ~35s interpreter teardown vs the 870s budget): the five
    # heaviest remaining tests, each leaving fast siblings in its
    # subsystem (pallas keeps flash_mask_fast_path_parity +
    # grad_parity_interpret; hybrid TP keeps model_axis_comm + the
    # two-axis llama step; diffusion pipeline keeps text_encoder_shapes +
    # ddim_step; continuous batching and MoE keep their many others)
    "test_pallas_train.py::test_flash_mask_dropout_bf16_gqa_train",
    "test_hybrid.py::TestTensorParallel::"
    "test_tp_llama_logits_and_loss_parity",
    "test_diffusion.py::TestPipeline::test_no_cfg_path",
    "test_generation.py::TestContinuousBatching::"
    "test_streaming_mixed_lengths_matches_static_greedy",
    "test_moe.py::test_moe_dense_equivalence_single_expert",
    "test_robustness.py::TestTrainerPreemption::"
    "test_sigterm_drain_deadline_bounds_exit",
    "test_train_fastpath.py::TestFusedEagerParity::"
    "test_matches_per_param[SGD-kw0]",
    "test_train_fastpath.py::TestQuantizedComm::"
    "test_wire_quantized_all_reduce_close_to_psum",
    "test_generation.py::test_continuous_batching_ragged_decode_parity",
    # fourth tier (PR 15 added ~60s of spec-decode coverage and the
    # canonical body crept back over ~835s + ~35s teardown vs the 870s
    # window): the heaviest spec tests plus the 3-10s generation
    # parity tail, each leaving fast siblings in the default run
    # (chunk interplay keeps greedy_spec_bitwise_parity + the
    # spec+sampling bundle's warm start; the rejection-sampling
    # statistical check and the
    # cross-path sampled-parity regression keep verify_spans_greedy,
    # the fused-filter equivalence, and the serve-loop determinism
    # tests; generation keeps ragged_prompts_match_solo,
    # top_k1_equals_greedy, eos_early_stop and the CB parity family;
    # beam keeps its scored/batched siblings)
    "test_spec_decode.py::TestSpecServeLoop::"
    "test_spec_and_sampling_with_chunked_prefill",
    "test_spec_decode.py::TestSamplingKernels::"
    "test_rejection_sampling_preserves_target_distribution",
    "test_spec_decode.py::TestSamplingServeLoop::"
    "test_eager_static_serve_sampled_parity",
    "test_generation.py::TestGreedyGeneration::"
    "test_static_cache_matches_eager",
    "test_generation.py::TestReviewRegressions::"
    "test_eager_fallback_ragged_matches_solo",
    "test_generation.py::TestBeamSearch::"
    "test_eager_beam_min_new_tokens",
    "test_generation.py::TestSpeculativeDecoding::"
    "test_speculative_eos_stops",
    "test_generation.py::TestLLMPredictor::"
    "test_batched_serving_matches_solo",
    "test_generation.py::TestQuantizedPredictor::"
    "test_llm_predictor_weight_only",
    "test_generation.py::TestEagerFallback::"
    "test_gpt_static_cache_matches_eager",
    "test_generation.py::TestEagerFallback::"
    "test_gpt_tuple_cache_incremental_decode",
    "test_generation.py::TestBeamSearch::"
    "test_static_beam_matches_eager_beam",
    "test_pp_memory.py::test_remat_reduces_activation_memory",
    "test_nn.py::TestAdaptiveSoftmaxAndDecode::"
    "test_adaptive_log_softmax_torch_golden",
    "test_nn.py::TestLayers::test_transformer_full",
    "test_functional_extra.py::TestDetectionOpsRound3::"
    "test_yolo_loss_targets",
    "test_functional_extra.py::TestBicubicParity::"
    "test_bicubic_matches_torch",
    "test_diffusion.py::TestUNet::test_per_sample_timesteps",
    "test_diffusion.py::TestPipeline::test_t2i_runs_and_deterministic",
    "test_trainer.py::TestTrainerHybridParallel::test_dp2_mp2_sharding3",
    "test_long_context.py::test_ring_attention_zigzag_vs_contiguous",
    "test_long_context.py::test_ulysses_grads_match",
    "test_long_context.py::TestVarlenContextParallel::"
    "test_tensor_api_kv_lens",
    "test_long_context.py::TestVarlenContextParallel::"
    "test_ring_varlen_zigzag_causal",
    "test_long_context.py::test_ring_attention_matches_full[4",
    "test_long_context.py::test_ring_attention_matches_full[2-True]",
    "test_jit.py::TestVisionAndModel::test_resnet18_forward",
    "test_jit.py::TestVisionAndModel::test_resnet50_param_count",
    "test_moe.py::test_moe_layer_forward_backward[naive]",
    "test_hf_parity.py::TestGPT2HFParity::"
    "test_logits_and_generate_match",
    "test_hf_parity.py::TestBertHFParity::"
    "test_sequence_classification_logits_match",
    "test_distribution.py::TestSecondTierKL::"
    "test_kl_closed_forms_match_monte_carlo",
    "test_models_nlp.py::TestBertHeads::"
    "test_heads_shapes_and_tied_mlm_grad",
    "test_models_nlp.py::TestErnie::test_seq_cls_finetune_step",
    "test_pallas_train.py::test_flash_dropout_fast_path",
    "test_pallas_train.py::test_llama_gqa_trains",
    "test_pipeline.py::test_pipeline_remat_activation_memory",
    "test_pipeline.py::test_pipeline_zero_sharding_loss_parity",
    "test_pipeline.py::test_pipeline_train_loss_parity[4-2]",
    "test_vision_models.py::TestVisionZoo::test_shufflenet",
    "test_serving_fastpath.py::TestDeviceResidentAdmission::"
    "test_gqa_decode_parity",
    "test_quantization.py::TestQAT::"
    "test_convert_bakes_quantized_weights",
    "test_optimizer.py::TestOneCycleR5::"
    "test_opt_state_restore_into_fresh_optimizer",
    "test_incubate_fused.py::TestReviewRegressions::"
    "test_fused_mha_cache_decode",
    "test_multiprocess.py::test_two_process_rpc",
    "test_fuzz_smoke.py::test_fuzz_family_smoke[einsum_io",
    # PR-17 tensor-parallel serving: the heaviest parity variants
    # (static-reference plain decode, spec-verify) move to tier 2 —
    # tier 1 keeps the serve_stream TP=2-vs-TP=1 parity, the
    # head-sharded pool invariants, the topology-invalidation round
    # trip, and the TP-2 bundle's warm start (bitwise the one-device
    # replica's tokens)
    "test_tp_serving.py::TestTPGreedyParity::test_plain_decode_parity",
    "test_tp_serving.py::TestTPGreedyParity::test_spec_verify_parity",
    # PR 20 window trim (the canonical body crept to ~908s vs the 870s
    # budget): the heaviest remaining parity/round-trip tests, each
    # leaving a fast sibling in tier 1 —
    # TP serving keeps telemetry/comm accounting, the head-sharded pool
    # invariants, topology invalidation, and the TP-2 bundle's warm
    # start (bitwise the one-device replica's tokens); chunked prefill
    # keeps parity_with_unchunked_and_telemetry; serving fastpath keeps
    # the queue-policy + prefix-cache + admission families; MoE keeps
    # [gshard]; pallas keeps mask_fast_path + grad_parity_interpret;
    # lint keeps the zero-findings gate + the CLI subprocess smoke;
    # diffusion keeps text_encoder_shapes + ddim_step; hybrid keeps
    # model_axis_comm + the two-axis llama step
    "test_tp_serving.py::TestTPGreedyParity::test_serve_stream_parity",
    "test_tp_serving.py::TestTPGreedyParity::test_chunked_prefill_parity",
    "test_mixed_step.py::TestChunkedPrefill::"
    "test_parity_on_interpret_ragged_route",
    "test_serving_fastpath.py::TestRaggedMetaBuilder::"
    "test_matches_from_scratch_flatten_through_kernel",
    "test_moe.py::test_moe_layer_forward_backward[switch",
    "test_nn.py::TestLayers::test_rnn_lstm_gru",
    "test_pallas_train.py::test_flash_bf16_headdim64_pad_path",
    "test_lint.py::test_baseline_cli_round_trip",
    "test_lint.py::test_write_baseline_preserves_notes_and_scope",
    "test_diffusion.py::TestVAE::test_roundtrip_shapes",
    "test_hybrid.py::TestExplicit1F1B::"
    "test_schedule_bitwise_output_and_grad_parity",
)


def pytest_collection_modifyitems(config, items):
    import pytest as _pt
    for item in items:
        base = item.fspath.basename
        for mark, files in _SHARDS.items():
            if base in files:
                item.add_marker(getattr(_pt.mark, mark))
        nid = item.nodeid
        if any(s in nid for s in _SLOW_TESTS):
            item.add_marker(_pt.mark.slow)


# ---------------------------------------------------------------------------
# Trainer workers under the real launcher (the slow recovery and fleet
# tests): one worker script, parametrised by the fault it arms.
# ---------------------------------------------------------------------------
_TRAINER_WORKER = """
import json, os, sys, time
hb_path = os.environ.get("PADDLE_RANK_HEARTBEAT")


def boot_beat(phase):
    # raw early beats: the launcher must see progress before
    # paddle_tpu's own heartbeat can be imported
    if hb_path:
        with open(hb_path, "a") as f:
            f.write(json.dumps({{"ts": time.time(), "kind": "heartbeat",
                                "phase": phase, "pid": os.getpid(),
                                "rank": os.environ.get("RANK", "0")}})
                    + chr(10))


boot_beat("boot")
sys.path.insert(0, {repo!r})
# dp_degree virtual devices a rank, so a rank's comm telemetry is real
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count={dp_degree}"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn
from paddle_tpu.trainer import Trainer, TrainingArguments
boot_beat("imports_done")
rank = int(os.environ.get("RANK", "0"))
world = int(os.environ.get("WORLD_SIZE", "1"))
epoch = int(os.environ.get("PADDLE_RESTART_EPOCH", "0"))
if {fault_epochs} is None or epoch in {fault_epochs}:
    paddle.set_flags({{"fault_injection": {fault!r}}})
paddle.seed(rank)
model = nn.Sequential(nn.Linear(8, 32), nn.Tanh(), nn.Linear(32, 4))
opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                             parameters=model.parameters())
boot_beat("model_built")


def data_fn(start):
    def gen():
        s = start
        while True:
            time.sleep({step_s})
            rs = np.random.RandomState(s)
            yield (paddle.to_tensor(rs.randn(16, 8).astype(np.float32)),
                   paddle.to_tensor(rs.randn(16, 4).astype(np.float32)))
            s += 1
    return gen()


out_dir = os.path.join({out!r}, "rank%d" % rank)
# the JOB's step budget is fixed: each live rank takes an equal share
args = TrainingArguments(output_dir=out_dir,
                         max_steps={total_steps} // world,
                         logging_steps=1, save_steps={save_steps},
                         dp_degree={dp_degree})
res = Trainer(model, opt, lambda o, y: F.mse_loss(o, y), args, data_fn,
              tokens_per_batch=16).train(resume=True)
with open(os.path.join(out_dir, "result_e%d.json" % epoch), "w") as f:
    json.dump({{"rank": rank, "world": world,
               "start_step": res["start_step"],
               "final_step": res["final_step"]}}, f)
"""



@pytest.fixture
def launch_trainer_workers(tmp_path):
    """Run Trainer workers under the real launcher, logs in
    `<tmp_path>/log`; returns its exit code and every result file the
    workers wrote."""
    def run(launcher_args, *, fault, fault_epochs, total_steps,
            save_steps, step_s=0, dp_degree=1):
        import glob
        import json
        from paddle_tpu.distributed.launch.main import parse_args, launch
        script = tmp_path / "worker.py"
        script.write_text(_TRAINER_WORKER.format(
            repo=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            out=str(tmp_path), fault=fault, fault_epochs=fault_epochs,
            total_steps=total_steps, save_steps=save_steps,
            step_s=step_s, dp_degree=dp_degree))
        rc = launch(parse_args(launcher_args + [
            "--heartbeat_interval", "0.25", "--restart_backoff", "0.05",
            "--log_dir", str(tmp_path / "log"), str(script)]))
        return rc, [json.load(open(p)) for p in sorted(glob.glob(
            str(tmp_path / "rank*" / "result_e*.json")))]
    return run
