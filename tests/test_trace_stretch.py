"""Tier-1's hold on what judges a traced run of the benchmark: the stretch of
whole commit periods and the four device numbers read off it
(``benchmarks/trace_reduce.py``, the readers in ``benchmarks/layer_metrics``).
The 23 cases live with the benchmark
(``benchmarks/tests/test_trace_stretch.py``: pure Python, no device, no JAX)
and are collected here as they are, so that every PR runs them: since the
commit left the build loop's thread the commits fall inside the train
program's runs, which is the case they were written for.
"""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_trace_stretch")

from benchmarks.tests.test_trace_stretch import (  # noqa: E402,F401
    test_a_run_missing_at_an_edge_reads_none,
    test_a_stretch_without_the_program_or_without_an_op_says_so,
    test_a_trace_cut_short_against_the_waited_seconds_reads_none,
    test_gaps_are_named_by_the_programs_own_spans_of_any_thread,
    test_the_four_numbers_do_not_depend_on_the_phase,
)
