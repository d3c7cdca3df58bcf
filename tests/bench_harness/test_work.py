"""FLOP and byte functions against hand-computed shapes; the peaks table."""

import pytest

from benchmark import work
from benchmark.readers import (
    counter,
    host_span,
    idle_share,
    mfu,
    module_time,
    result_count,
    roofline,
)


def test_one_pass_over_a_dense_block():
    assert work.pass_flops(786432, 2048) == 4 * 786432 * 2048
    assert work.pass_bytes(786432, 2048, 4) == 6442450944
    assert work.passes(30) == 31


def test_block_work_over_solves_and_entities():
    one = work.block_work(10, 3, 4, 2)
    assert one == {"flops": 3 * 4 * 10 * 3, "bytes": 3 * 10 * 3 * 4}
    grid = work.block_work(10, 3, 4, [2, 0, 1])
    assert grid["flops"] == (3 + 1 + 2) * 120
    assert work.add_work(one, grid)["bytes"] == one["bytes"] + grid["bytes"]


def test_the_v5e_peaks_and_an_unknown_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        work.peaks("cpu")


CONTEXT = {
    "trace": {"window_s": 2.0, "busy_s": 1.5, "steps": 3, "chips": 1,
              "modules": [(0, 600_000_000, "jit__fit_blocks_impl(1)")]},
    "window_s": 10.0, "units": 6.0, "units_per_step": 2.0,
    "records": [{"iterations": [3, 1]}, {"iterations": [4, 2]},
                {"iterations": [5, 3]}],
    "work": {"flops": 197e12, "bytes": 0},
    "traced_work": {"flops": 0, "bytes": 819e9 * 0.75},
    "host_spans": {"build": 2.5, "data": 1.0},
    "counters": {"compile_secs": 4.5},
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "chips": 1}


def test_readers_on_a_hand_built_context():
    assert idle_share.read({}, CONTEXT) == pytest.approx(25.0)
    assert mfu.read({}, CONTEXT) == pytest.approx(10.0)
    assert roofline.read({}, CONTEXT) == pytest.approx(50.0)
    assert result_count.read({"field": "iterations"}, CONTEXT) == 3.0
    assert host_span.read({"spans": ["build"]}, CONTEXT) == 2.5
    assert counter.read({"counter": "compile_secs"}, CONTEXT) == 4.5
    assert module_time.read({"pattern": "fit_blocks"}, CONTEXT) == \
        pytest.approx(100.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    untraced = dict(CONTEXT, trace=None, traced_work=None)
    assert idle_share.read({}, untraced) is None
    assert roofline.read({}, untraced) is None
    assert module_time.read({"pattern": "x"}, untraced) is None
    no_plane = dict(CONTEXT, trace=dict(CONTEXT["trace"], modules=[]))
    assert module_time.read({"pattern": "fit_blocks"}, no_plane) is None


def test_a_module_pattern_that_matches_none_of_the_modules_that_ran_raises():
    # modules ran on the device, none of that name: the name has moved, and
    # the metric must not drop out of the line in silence
    with pytest.raises(LookupError, match="no_such_module"):
        module_time.read({"pattern": "no_such_module"}, CONTEXT)
    assert idle_share.read({}, dict(CONTEXT, trace=dict(
        CONTEXT["trace"], busy_s=0.0))) is None
    assert result_count.read({"field": "absent"}, CONTEXT) is None
    assert host_span.read({"spans": ["absent"]}, CONTEXT) is None
