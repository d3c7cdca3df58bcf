"""The trace reduction on hand-built (start, duration, name) tuples."""

import pytest

from benchmark import trace_reduce as tr

MS = 1_000_000


def test_union_counts_overlaps_once_and_skips_gaps():
    events = [(0, 10 * MS, "a"), (5 * MS, 10 * MS, "b"),  # overlap: 0-15
              (20 * MS, 5 * MS, "c"),  # gap 15-20
              (21 * MS, 1 * MS, "d")]  # nested
    assert tr.union_seconds(events) == pytest.approx(0.020)
    assert tr.union_seconds([]) == 0.0


def test_idle_gaps_longest_first_and_clipped_to_the_window():
    events = [(2 * MS, 3 * MS, "a"), (9 * MS, 1 * MS, "b")]
    gaps = tr.idle_gaps(events, 0, 12 * MS)
    assert gaps == [(5 * MS, 4 * MS), (0, 2 * MS), (10 * MS, 2 * MS)]
    assert tr.idle_gaps(events, 3 * MS, 4 * MS) == []


def test_clip_cuts_events_to_the_window():
    assert tr.clip([(0, 10, "a"), (20, 5, "b")], 5, 22) == [
        (5, 5, "a"), (20, 2, "b")]


def test_self_time_takes_the_body_out_of_the_while():
    events = [(0, 100 * MS, "%while.1 = () while(x)"),
              (10 * MS, 30 * MS, "%k = () custom-call(x), "
               'custom_call_target="tpu_custom_call"'),
              (50 * MS, 20 * MS, "%fusion.2 = f32[] fusion(y)"),
              (200 * MS, 5 * MS, "%fusion.2 = f32[] fusion(y)")]
    got = tr.self_seconds_by_name(events)
    assert got["%while.1 while"] == pytest.approx(0.050)
    assert got["%k custom-call tpu_custom_call"] == pytest.approx(0.030)
    assert got["%fusion.2 fusion"] == pytest.approx(0.025)
    assert sum(got.values()) == pytest.approx(tr.union_seconds(events))


def test_short_op_name_keeps_name_opcode_and_target():
    line = ('%body.6 = (f32[1,1]{1,0:T(1,128)}, f32[1,2048]{1,0:T(1,128)S(1)}) '
            'custom-call(f32[786432,2048]{1,0:T(8,128)} %get-tuple-element), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tr.short_op_name(line) == "%body.6 custom-call tpu_custom_call"
    assert tr.short_op_name("plain") == "plain"


def test_modules_group_by_name_without_the_fingerprint():
    modules = [(0, 2 * MS, "jit__minimize_lbfgs_impl(123)"),
               (5 * MS, 3 * MS, "jit__minimize_lbfgs_impl(123)"),
               (9 * MS, 1 * MS, "jit__fit_blocks_impl(77)"),
               (11 * MS, 1 * MS, "jit__fit_blocks_impl(78)")]
    assert tr.seconds_by_module(modules) == {
        "jit__minimize_lbfgs_impl": pytest.approx(0.005),
        "jit__fit_blocks_impl": pytest.approx(0.002)}
    assert tr.module_seconds_matching(
        modules, "^jit__fit_blocks_impl$") == pytest.approx(0.002)
    assert tr.module_seconds_matching(modules, "nothing") == 0.0


def test_gaps_are_named_by_the_shortest_host_event_over_their_middle():
    host = [(0, 100, "bench.step"), (40, 20, "fetch"), (45, 50, "wait")]
    assert tr.label_gaps([(48, 4), (200, 10)], host) == [
        ("fetch", 4e-9), ("unlabelled", 1e-8)]


def test_top_is_sorted_and_cut():
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                          ["c", 2.0]]


def test_a_directory_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))


def test_describe_lists_the_planes_and_lines_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        jnp.arange(8.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    rows = tr.describe(str(tmp_path))
    assert rows and all(set(r) == {"plane", "line", "events", "top"}
                        for r in rows)
    assert any(r["plane"].startswith("/host:") for r in rows)
    assert any(name == "bench.step" for r in rows for name, _ in r["top"]) \
        or sum(r["events"] for r in rows) > 0
