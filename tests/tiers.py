"""Tier manifest: node-id patterns auto-marked ``slow`` by conftest.py.

Tier-1 (`make test-fast`, `pytest -m "not slow"`) is the per-push CI
gate and must stay under ~90s on an idle CPU; tier-2 (`make test`) is
everything.  Tests land here when they measure over ~2-3s on the CI
reference box (`pytest --durations=0`) — mostly convergence runs, the
full selection-policy x estimator matrices, decode-consistency sweeps,
and the heavyweight arch smokes.

Patterns are fnmatch'd against the full node id, so individual
parametrized cases can be tiered while cheap siblings of the same test
stay in tier-1 as representatives (e.g. ``two_point_bit_identical
[False-mezo]`` remains fast while the other five cases are tier-2).
Decorator ``@pytest.mark.slow`` still works and is preferred for tests
that are slow by design (end-to-end training); this manifest exists so
per-case tiering doesn't require rewriting parametrize lists.
"""

SLOW_NODE_PATTERNS = [
    # -- end-to-end convergence / trainer runs
    "tests/test_trainer.py::test_lezo_tracks_mezo",
    "tests/test_trainer.py::test_zo_momentum_beats_zo_sgd",
    "tests/test_trainer.py::test_lezo_converges",
    "tests/test_trainer.py::test_quorum_still_converges",
    "tests/test_trainer.py::test_fo_baseline_converges",
    "tests/test_trainer.py::test_peft_runs_and_moves_loss[*]",
    "tests/test_trainer.py::test_eval_accuracy_classification",
    # -- arch smokes: every zoo config costs 4-40s to lower+run; the opt
    #    stack itself is covered fast by the task/trainer/kernel tests
    "tests/test_archs_smoke.py::test_arch_smoke[*",
    # -- estimator subsystem: full matrices are tier-2; the cheapest
    #    bit-identical case and the static-slot kernel cases stay tier-1
    "tests/test_estimators.py::test_trainer_selects_estimators",
    "tests/test_estimators.py::test_one_sided_q_chunk_equivalent",
    "tests/test_estimators.py::test_backend_matches_dense_per_estimator[*",
    "tests/test_estimators.py::test_two_point_bit_identical_to_legacy[True-*",
    "tests/test_estimators.py::test_two_point_bit_identical_to_legacy[False-stratified]",
    "tests/test_estimators.py::test_two_point_bit_identical_to_legacy[False-uniform]",
    "tests/test_estimators.py::test_averaged_q1_matches_two_point",
    "tests/test_estimators.py::test_dropped_layers_untouched_under_estimators",
    "tests/test_estimators.py::test_one_sided_converges_quadratic",
    # -- distributed / sharding subprocess cells
    "tests/test_sharding.py::test_dryrun_cell_subprocess",
    "tests/test_distributed_train.py::test_dp_tp_training_matches_single_device",
    # -- model stack: decode-consistency sweeps and chunk invariances
    "tests/test_models.py::test_mlstm_chunk_invariance",
    "tests/test_models.py::test_mamba_chunk_invariance",
    "tests/test_models.py::test_flash_key_padding_with_prefix_offset",
    "tests/test_models.py::test_multi_step_decode_matches_train",
    "tests/test_models.py::test_decode_consistency_dense",
    "tests/test_models.py::test_decode_consistency_xlstm",
    "tests/test_models.py::test_decode_consistency_mla",
    "tests/test_models.py::test_decode_consistency_moe_dropless",
    "tests/test_models.py::test_decode_consistency_mamba",
    "tests/test_models.py::test_chunked_ce_matches_dense",
    "tests/test_models.py::test_flash_matches_naive[*",
    "tests/test_moe.py::test_dispatch_matches_dense_oracle",
    "tests/test_moe.py::test_single_token_never_drops",
    "tests/test_moe.py::test_shared_experts_added",
    "tests/test_moe.py::test_capacity_drop_bounded",
    "tests/test_zo_adaptive.py::test_momentum_matches_explicit_buffer",
    "tests/test_peft.py::test_prefix_changes_forward",
    # -- ZO core / kernels: the (vector, count) slot forms and the rows
    #    around 64Ki elements; static slots stay tier-1 as representatives
    "tests/test_zo.py::test_fused_equals_unfused[*",
    "tests/test_zo.py::test_perturb_restore_identity",
    "tests/test_kernels.py::test_backend_matches_dense[pair_one_off-*",
    "tests/test_kernels.py::test_backend_matches_dense[pair_all_on-float32-*",
    # ragged rows are pinned fast by test_kernels_golden.py
    "tests/test_kernels.py::test_sweep_row_lengths[*",
    "tests/test_rng.py::test_layer_ids_subset",
    "tests/test_estimators.py::test_one_sided_bias_quadratic",
    # -- fused virtual-perturbation runtime: the acceptance gates
    #    (test_two_point_virtual_matches_materialized_dense, the zero-write
    #    single-axpy check, the z-consistency contract and the f32 kernel
    #    property cases) stay tier-1; the full-model loss sweeps, the
    #    per-estimator matrices and the bf16/trans kernel grid are tier-2
    "tests/test_fused.py::test_virtual_loss_equals_materialized[*",
    "tests/test_fused.py::"
    "test_two_point_virtual_matches_materialized_dense[virtual_ref]",
    "tests/test_fused.py::test_estimators_virtual_matches_materialized[*",
    "tests/test_fused.py::test_virtual_pallas_loss_close_to_materialized",
    "tests/test_fused.py::test_trainer_virtual_backend_trains",
    "tests/test_fused.py::test_virtual_jaxpr_has_single_param_write",
    "tests/test_fused.py::test_pmatmul_matches_ref[*bfloat16]",
    "tests/test_fused.py::test_pmatmul_matches_ref[True-*",
    # -- paired ±εz probes: tier-1 keeps the cheap representatives (the
    #    eager span+bit-identity step, the RNG-stream property, the
    #    aligned/trans kernel stacks, the probe accessor); the jitted
    #    per-estimator matrix, full-model loss pairs, q-probe stacks and
    #    the disable_jit counter walk are tier-2
    "tests/test_fused.py::test_paired_structural_counters_halve",
    "tests/test_fused.py::test_stacked_probes_bitwise_match_sequential",
    "tests/test_fused.py::test_paired_step_bitwise_matches_unpaired[*",
    "tests/test_fused.py::test_paired_loss_bitwise_matches_two_forwards[*",
    "tests/test_fused.py::test_pmatmul_stack_bitwise_matches_pmatmul[shape1-*",
    "tests/test_flash_kernel.py::test_flash_kernel_matches_ref[float32-True-3-64-32-64-32]",
    "tests/test_flash_kernel.py::test_flash_kernel_matches_model_flash",
    # -- unified experiment spec (repro.api, DESIGN.md §11): the
    #    serialization / validation / CLI-parse tests are milliseconds
    #    and stay tier-1; the canonical two_point-materialized legacy-vs-
    #    spec equivalence case and the train-command e2e stay tier-1 as
    #    representatives, the rest of the matrix and the multi-run
    #    checkpoint/sweep/shim cases are tier-2
    "tests/test_api.py::test_legacy_vs_spec_bit_identical[two_point-virtual_ref]",
    "tests/test_api.py::test_legacy_vs_spec_bit_identical[one_sided-*",
    "tests/test_api.py::test_legacy_vs_spec_bit_identical[averaged-*",
    "tests/test_api.py::test_legacy_vs_spec_bit_identical[importance-*",
    "tests/test_api.py::test_checkpoint_embeds_spec_and_rejects_mismatch",
    "tests/test_api.py::test_legacy_checkpoints_have_no_spec_and_still_resume",
    "tests/test_api.py::test_sweep_returns_structured_results",
    "tests/test_api_cli.py::test_legacy_train_shim_accepts_historical_flags",
    "tests/test_api_cli.py::test_legacy_serve_shim_smoke",
    # -- serving engine (DESIGN.md §12): the greedy bit-identity gate,
    #    the prefill/decode interleave check and the CLI e2e stay tier-1;
    #    the temperature/batch-composition sweep, the rope-arch identity
    #    and the EOS path are tier-2 (each recompiles a fresh engine)
    "tests/test_serving.py::"
    "test_engine_sampling_reproducible_across_batch_composition",
    "tests/test_serving.py::test_engine_bit_identical_on_rope_arch",
    "tests/test_serving.py::test_engine_eos_stops_early_and_frees_pages",
    # -- swarm (tests/test_swarm.py): the subprocess e2e runs carry
    #    @pytest.mark.slow directly (slow by design: each spawns 2-3
    #    worker processes for ~30-40s); the protocol/commit/chaos/spec
    #    property tests all stay tier-1 (<1s total)
]
