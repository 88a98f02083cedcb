"""The yardstick's own chip-free cases, collected in tier-1 so that the next
rot of ``benchmark/tests`` shows in the driver's run: every case
``benchmark/tests/tier1_cases.py`` lists (what needs no chip, no train loop
and no compile), the Trinity cell's, the Mellum2 cell's and the reader
``flash.xla_ms``'s, by name.  No assertion lives here.

Trinity's ``test_the_cell_its_job_and_its_metrics`` held three entries'
``workloads`` to ONE cell and ``test_flash_xla_ms.py``'s first case its
entry to the LAST place of ``per_layer``; PR 62 repaired both to name and
membership, and they are collected again, through ``tier1_cases.py``.  The cases ``test_mellum.py``
wrote while they were out
(``test_trinitys_window_entries_stand_as_they_were_with_this_cell_appended``,
``test_flash_xla_ms_stands_as_it_was_before_this_cells_two_entries``) stay
beside them, as do the Kimi-Linear cell's (PR 59), the Solar-Open-2
cell's (PR 64), the Nemotron-3-Super cell's (PR 66) and the Keye-VL-2.0
cell's (PR 70), the Phi-4-mini-flash cell's (PR 74), the SDAR
block-diffusion cell's (PR 76), the Ouro looped-stack cell's (PR 79) and the
Laguna cell's (PR 83: it brings no entry and JOINS twelve lists, so the
three older cases that hold such a list closed run through its
``before_this_cell``), by
name; and ``test_late_steps.py``'s (PR 68: the readers
of a window's lost time on canned spans), whole."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_trinity",
                               "benchmark.tests.test_mellum",
                               "benchmark.tests.test_flash_xla_ms",
                               "benchmark.tests.test_kimi_linear",
                               "benchmark.tests.test_solar_open2",
                               "benchmark.tests.test_nemotron3",
                               "benchmark.tests.test_keye",
                               "benchmark.tests.test_phi4flash",
                               "benchmark.tests.test_sdar",
                               "benchmark.tests.test_ouro",
                               "benchmark.tests.test_laguna",
                               "benchmark.tests.test_late_steps")

from benchmark.tests.tier1_cases import *  # noqa: E402,F401,F403
from benchmark.tests.test_trinity import (  # noqa: E402,F401
    test_flops_count_the_windows_pairs_not_the_causal_ones,
    test_on_a_program_without_the_window_the_readers_return_nothing,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_thirty_two,
    test_the_parameter_count_is_init_params as test_trinity_parameter_count,
    test_window_readers_on_synthetic_planes)
from benchmark.tests.test_mellum import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn,
    test_flash_xla_ms_stands_as_it_was_before_this_cells_two_entries,
    test_flops_count_what_the_window_leaves_and_the_held_experts_compute,
    test_on_a_program_without_them_the_two_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as test_mellum_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_four,
    test_the_parameter_count_is_init_params as test_mellum_parameter_count,
    test_the_two_readers_on_recorded_values,
    test_trinitys_window_entries_stand_as_they_were_with_this_cell_appended)
from benchmark.tests.test_flash_xla_ms import (  # noqa: E402,F401
    test_nothing_left_reads_zero_and_no_scope_reads_none,
    test_on_a_mesh_the_slowest_chip_is_read,
    test_the_scope_less_the_flash_kernels_whatever_their_names)
from benchmark.tests.test_kimi_linear import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn as
    test_kimi_linear_each_floor_and_each_width,
    test_flops_count_the_recurrence_the_held_rows_and_the_two_latent_layers,
    test_on_a_program_without_the_rule_the_readers_return_nothing,
    test_rope_kernel_ms_stands_as_it_was_before_this_cells_five_entries,
    test_the_cell_its_job_and_its_metrics as
    test_kimi_linear_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_sixteen,
    test_the_five_readers_on_synthetic_planes,
    test_the_parameter_count_is_init_params as
    test_kimi_linear_parameter_count)
from benchmark.tests.test_solar_open2 import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn as
    test_solar_open2_each_floor_and_each_width,
    test_flops_count_the_recurrence_the_held_rows_and_the_one_softmax_layer,
    test_on_a_program_without_the_rule_the_readers_return_nothing as
    test_solar_open2_readers_return_nothing_without_the_rule,
    test_the_cell_its_job_and_its_metrics as
    test_solar_open2_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_thirty_two,
    test_the_five_readers_on_a_made_up_run,
    test_the_parameter_count_is_init_params as
    test_solar_open2_parameter_count)
from benchmark.tests.test_nemotron3 import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn as
    test_nemotron3_each_floor_and_each_width,
    test_flops_count_the_latent_the_module_and_both_heads,
    test_on_a_program_without_the_latent_the_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as
    test_nemotron3_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_sixty_four,
    test_the_five_readers_on_synthetic_planes,
    test_the_parameter_count_is_init_params as
    test_nemotron3_parameter_count)
from benchmark.tests.test_keye import (  # noqa: E402,F401
    test_both_expert_keys_cut_is_a_complaint,
    test_each_floor_and_each_width_violated_in_turn as
    test_keye_each_floor_and_each_width,
    test_flash_dq_ms_lists_the_cells_that_were_there_and_not_this_one,
    test_flops_count_the_selected_pairs_and_the_indexer,
    test_on_a_program_without_the_indexer_the_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as
    test_keye_cell_job_and_metrics,
    test_the_eight_readers_on_synthetic_planes,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight,
    test_the_parameter_count_is_init_params as
    test_keye_parameter_count)
from benchmark.tests.test_keye import as_accepted  # noqa: E402
from benchmark.tests.test_phi4flash import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn as
    test_phi4flash_each_floor_and_each_width,
    test_flops_count_the_needed_pairs_the_projections_and_the_scan_apart,
    test_on_a_program_without_the_mixers_the_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as
    test_phi4flash_cell_job_and_metrics,
    test_the_eleven_readers_on_a_made_up_run,
    test_the_readers_and_the_flop_module_import_no_jax,
    test_the_file_is_the_catalog_row_cut_to_the_rule_at_depth_eight,
    test_the_parameter_count_is_init_params as
    test_phi4flash_parameter_count)
from benchmark.tests.test_sdar import (  # noqa: E402,F401
    test_each_floor_and_each_width_violated_in_turn as
    test_sdar_each_floor_and_each_width,
    test_flops_count_two_rows_a_token_one_through_the_head_and_the_needed_pairs,
    test_on_a_program_without_the_objective_the_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as
    test_sdar_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight as
    test_sdar_file_is_the_catalog_row_cut_to_one_chip_of_eight,
    test_the_parameter_count_is_init_params as
    test_sdar_parameter_count,
    test_the_readers_and_the_flop_module_import_no_jax as
    test_sdar_readers_and_flop_module_import_no_jax,
    test_the_roofline_cannot_pass_100_unless_the_count_is_wrong,
    test_the_six_readers_on_a_made_up_run)
from benchmark.tests.test_ouro import (  # noqa: E402,F401
    test_each_width_and_the_passes_changed_in_turn_is_a_complaint,
    test_flops_count_a_layer_and_the_head_once_a_pass,
    test_on_a_program_without_the_passes_the_readers_return_nothing,
    test_the_cell_its_job_and_its_metrics as
    test_ouro_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_in_depth_alone,
    test_the_five_readers_on_a_made_up_run,
    test_the_parameter_count_is_init_params as
    test_ouro_parameter_count,
    test_the_readers_and_the_flop_module_import_no_jax as
    test_ouro_readers_and_flop_module_import_no_jax,
    test_the_roofline_cannot_pass_100_unless_the_count_is_wrong as
    test_ouro_roofline_cannot_pass_100_unless_the_count_is_wrong)
from benchmark.tests.test_laguna import (  # noqa: E402,F401
    test_each_floor_each_width_and_each_head_count_violated_in_turn,
    test_flops_count_attention_by_the_layers_kind,
    test_the_cell_its_job_and_its_metrics as
    test_laguna_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight as
    test_laguna_file_is_the_catalog_row_cut_to_one_chip_of_eight,
    test_the_joined_readers_count_by_kind_through_this_cells_module,
    test_the_parameter_count_is_init_params as
    test_laguna_parameter_count,
    test_the_readers_and_the_flop_module_import_no_jax as
    test_laguna_readers_and_flop_module_import_no_jax)
from benchmark.tests.test_laguna import before_this_cell  # noqa: E402
from benchmark.tests.test_late_steps import (  # noqa: E402,F401
    test_a_stall_is_split_into_stopped_running_and_waiting,
    test_a_steady_window_reads_zero_everywhere,
    test_a_stop_longer_than_the_interval_is_late_by_counts_for_no_more,
    test_setup_lag_gives_no_number_where_lags_were_lost,
    test_the_parents_spans_read_nothing as
    test_late_steps_parents_spans_read_nothing,
    test_the_seven_entries_in_benchmark_json,
    test_the_three_parts_make_late_ms_to_the_float,
    test_the_tool_prints_the_readers_numbers_and_a_row_a_late_interval)

# Six older cases hold "the entries that list my cell" as a closed set; the
# list PR 70 had to give ``flash.dq_ms`` (null in every cell since PR 69)
# names their cells.  Their files are the benchmark's: each runs here on
# the file as its PR knew that one entry (``test_keye.as_accepted``).
test_nemotron_h_cell_job_and_metrics = as_accepted(  # noqa: F405
    test_nemotron_h_cell_job_and_metrics)  # noqa: F405
test_the_file_is_the_catalog_row_cut_to_one_chip_of_two = as_accepted(
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_two)  # noqa: F405
test_mellum_cell_job_and_metrics = before_this_cell(as_accepted(
    test_mellum_cell_job_and_metrics))
test_kimi_linear_cell_job_and_metrics = as_accepted(
    test_kimi_linear_cell_job_and_metrics)
test_solar_open2_cell_job_and_metrics = as_accepted(
    test_solar_open2_cell_job_and_metrics)
test_nemotron3_cell_job_and_metrics = as_accepted(
    test_nemotron3_cell_job_and_metrics)

# Two more hold ``rope.kernel_ms``'s three cells as a closed list, which the
# Laguna cell joined (its sliding layers rotate by the kernel): each runs on
# the file as it was before that (``test_laguna.before_this_cell``; what
# they no longer see, ``test_laguna_cell_job_and_metrics`` holds).
test_the_entry_is_written_as_the_flash_times_are = before_this_cell(
    test_the_entry_is_written_as_the_flash_times_are)  # noqa: F405
test_rope_kernel_ms_stands_as_it_was_before_this_cells_five_entries = \
    before_this_cell(
        test_rope_kernel_ms_stands_as_it_was_before_this_cells_five_entries)
