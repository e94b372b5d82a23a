"""The trace reduction on hand-made intervals."""

import pytest

from benchmark import trace_reduce as T


def test_union_merges_overlaps_and_ignores_empty():
    assert T.union_seconds([]) == 0.0
    assert T.union_seconds([(0, 1), (2, 3)]) == pytest.approx(2.0)
    assert T.union_seconds([(0, 2), (1, 3)]) == pytest.approx(3.0)
    assert T.union_seconds([(0, 5), (1, 2), (3, 4)]) == pytest.approx(5.0)
    assert T.union_seconds([(1, 1), (2, 1.5)]) == 0.0
    # order of arrival does not matter
    assert T.union_seconds([(4, 6), (0, 1), (5, 7)]) == pytest.approx(4.0)


def test_idle_gaps_and_share_are_clipped_to_the_window():
    busy = [(-1, 1), (2, 3), (2.5, 4), (9, 12)]
    assert T.idle_gaps(busy, (0, 10)) == [(1, 2), (4, 9)]
    assert T.idle_share(busy, (0, 10)) == pytest.approx(0.6)
    assert T.idle_gaps([], (0, 2)) == [(0, 2)]
    assert T.idle_share([], (0, 2)) == pytest.approx(1.0)
    assert T.idle_gaps([(0, 2)], (0, 2)) == []


def test_a_gap_is_labelled_by_the_spans_open_over_half_of_it():
    spans = [("commit", 0.0, 6.0), ("fetch", 4.0, 10.0), ("pack", 9.9, 10)]
    assert T.label_gap((0, 4), spans) == "commit"
    assert T.label_gap((4, 6), spans) == "commit+fetch"
    assert T.label_gap((5, 9), spans) == "fetch"
    assert T.label_gap((20, 21), spans) == "no_span"
    # two spans of one name add up
    assert T.label_gap((0, 2), [("a", 0, 0.6), ("a", 1, 1.6)]) == "a"


def test_gaps_are_summarized_longest_first_with_short_ones_summed():
    gaps = [(0, 2), (3, 3.0004), (4, 4.0005), (5, 5.5)]
    rows = T.summarize_gaps(gaps, [("wait", 0, 2), ("commit", 5, 6)])
    assert rows[0] == ["wait", pytest.approx(2.0)]
    assert rows[1] == ["commit", pytest.approx(0.5)]
    assert rows[2][0] == "between_ops_under_1ms"
    assert rows[2][1] == pytest.approx(0.0009)
    many = [(i, i + 0.01 * (i + 1)) for i in range(30)]
    assert len(T.summarize_gaps(many, [])) == T.TOP


def test_an_op_is_named_by_its_result_and_opcode():
    hlo = ("%while.61 = (s32[]{:T(128)}, s32[16,384,8,128]{3,2,1,0:T(8,128)"
           "S(1)}) while((s32[]{:T(128)}) %tuple.2055), condition=%c, "
           "body=%b")
    assert T.short_name(hlo) == "%while.61 while"
    assert T.short_name("%closed_call.230 = s32[32,384]{1,0:T(8,128)S(1)} "
                        "custom-call(s32[32]{0} %p)") == \
        "%closed_call.230 custom-call"
    assert T.short_name("k1") == "k1"


def test_top_ops_sum_by_name():
    ev = [("k1", 0, 1), ("k2", 1, 1.5), ("k1", 2, 4)]
    assert T.top_ops(ev) == [["k1", pytest.approx(3.0)],
                             ["k2", pytest.approx(0.5)]]
    assert len(T.top_ops([(f"k{i}", 0, 1 + i) for i in range(20)])) == T.TOP
