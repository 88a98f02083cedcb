"""The yardstick's cases that need no chip, no train loop and no compile,
BY NAME, for a tier-1 file to collect (``tests/test_yardstick.py``, one
line: ``from benchmark.tests.tier1_cases import *``), so that the next rot
lowers a count the driver reads.  No assertion is copied: each name is the
case of ``benchmark/tests`` itself, with its parametrisation, imported
through the namespace package the benchmark's tests already use; a case
whose name three models' files share is given its model's name.  Not here:
the train-loop rehearsals, the control readings, the float32 model
comparisons and the readers on the recorded trace (minutes together); nor
the cases of ``test_trinity.py``, ``test_mellum.py``, ``test_flash_xla_ms.py``
and ``test_kimi_linear.py`` that ``tests/test_yardstick.py`` imports by name
beside this list (PR 62 added the rows those files and
``test_rope_kernel_ms.py`` lacked here, and ``test_setup_reading.py``'s).
``test_tier1_cases.py`` holds this list to what the files define."""

import pytest

MODULES = ("test_benchmark", "test_experts_xla_ms", "test_flash_xla_ms",
           "test_gdn_kernel_ms", "test_granite_hybrid",
           "test_host_clock_readers", "test_joyai_flash", "test_lfm2_moe",
           "test_nemotron_h", "test_olmo_hybrid", "test_olmoe",
           "test_rope_kernel_ms", "test_rows_visited", "test_setup_readers",
           "test_setup_reading", "test_span_readers", "test_token_rows_read",
           "test_trinity", "test_xing4")
# imported, not collected: rewrite their asserts all the same
pytest.register_assert_rewrite(*("benchmark.tests." + m for m in MODULES))

from benchmark.tests.test_benchmark import (  # noqa: E402,F401
    test_at_most_a_quarter_of_the_cells_take_four_chips,
    test_every_configuration_in_benchmark_json_has_its_files,
    test_llama_config_resolves_published_and_held_counts,
    test_program_config_gives_what_it_gave,
    test_published_widths_and_reduced_keys,
    test_run_holds_no_cell_configuration_or_metric_name,
    test_the_leading_dense_layers_as_public_files_say_them,
    test_the_leading_period_of_a_per_layer_list,
    test_the_rule_of_a_cut,
    test_the_rule_takes_the_catalogs_row_with_six_leading_dense_layers)
from benchmark.tests.test_experts_xla_ms import (  # noqa: E402,F401
    test_the_entry_lists_the_expert_cells)
from benchmark.tests.test_flash_xla_ms import (  # noqa: E402,F401
    test_the_entry_is_the_kernels_own_in_every_cell)
from benchmark.tests.test_gdn_kernel_ms import (  # noqa: E402,F401
    test_the_entry_is_written_as_the_scan_times_is)
from benchmark.tests.test_granite_hybrid import (  # noqa: E402,F401
    test_the_file_is_the_catalog_row_and_the_model_its_first_ten_layers)
from benchmark.tests.test_host_clock_readers import (  # noqa: E402,F401
    test_entries_in_benchmark_json as test_host_clock_entries_in_benchmark_json)
from benchmark.tests.test_joyai_flash import (  # noqa: E402,F401
    test_the_cell_its_job_and_its_metrics as test_joyai_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_hosts_share_of_two)
from benchmark.tests.test_lfm2_moe import (  # noqa: E402,F401
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_two)
from benchmark.tests.test_nemotron_h import (  # noqa: E402,F401
    test_the_cell_its_job_and_its_metrics as test_nemotron_h_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chip_of_eight)
from benchmark.tests.test_olmo_hybrid import (  # noqa: E402,F401
    test_the_entries_this_cell_appends_leave_the_older_ones_as_they_were,
    test_the_file_is_the_catalog_row_cut_in_depth_alone)
from benchmark.tests.test_olmoe import (  # noqa: E402,F401
    test_published_widths_against_the_catalog_row)
from benchmark.tests.test_rope_kernel_ms import (  # noqa: E402,F401
    test_the_entry_is_written_as_the_flash_times_are,
    test_the_sum_where_the_trace_has_the_kernels_and_none_where_not)
from benchmark.tests.test_rows_visited import (  # noqa: E402,F401
    test_the_entry_lists_both_expert_cells)
from benchmark.tests.test_setup_readers import (  # noqa: E402,F401
    test_entries_in_benchmark_json as test_setup_entries_in_benchmark_json)
from benchmark.tests.test_setup_reading import (  # noqa: E402,F401
    test_a_span_that_is_not_one_opening_inside_set_up_fails,
    test_neither_the_span_nor_the_marks_fails_by_message,
    test_set_up_is_the_wall_less_the_runtimes_start,
    test_the_books_close_on_the_wall_not_on_the_new_reading,
    test_the_entry_keeps_its_name_and_a_bound_no_wider,
    test_the_subtracted_span_holds_one_call)
from benchmark.tests.test_span_readers import (  # noqa: E402,F401
    test_entries_in_benchmark_json as test_span_entries_in_benchmark_json)
from benchmark.tests.test_token_rows_read import (  # noqa: E402,F401
    test_the_entry_stands_with_the_three_expert_cells)
from benchmark.tests.test_trinity import (  # noqa: E402,F401
    test_the_cell_its_job_and_its_metrics as test_trinity_cell_job_and_metrics)
from benchmark.tests.test_xing4 import (  # noqa: E402,F401
    test_the_cell_its_job_and_its_metrics as test_xing4_cell_job_and_metrics,
    test_the_file_is_the_catalog_row_cut_to_one_chips_share_of_eight)

__all__ = sorted(name for name in dir() if name.startswith("test_"))
