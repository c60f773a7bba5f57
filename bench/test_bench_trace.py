"""The trace reduction on a small synthetic trace: union busy time, shape
classes, and idle gaps labelled by the benchmark's host spans."""

import pytest

from benchlib.trace import Event, classify, parse_op, reduce, union

BATCH = 8192
VOCABS = (10131227, 584, 8192, 4)


def op(shape, opcode, start_us, dur_us, name="fusion.1"):
    return Event(f"%{name} = {shape} {opcode}(x)", start_us * 1e3, dur_us * 1e3)


def test_parse_op_reads_opcode_and_leading_dim():
    assert parse_op("%f = f32[7046547,10]{0,1:T(8,128)} fusion(a, b)") == (
        "fusion", 7046547)
    assert parse_op("%w = (s32[], f32[8]{0}) while(t)") == ("while", None)
    assert parse_op("%c = f32[] constant(0)") == ("constant", None)


def test_classes():
    assert classify("%a = f32[10131227,10]{0,1} fusion(x)", BATCH, VOCABS) == "table"
    assert classify("%a = f32[584]{0} fusion(x)", BATCH, VOCABS) == "small_table"
    assert classify("%a = f32[8192,10]{1,0} gather(x)", BATCH, VOCABS) == "batch"
    assert classify("%a = s32[8192]{0} sort(x)", BATCH, VOCABS) == "sort"
    assert classify("%a = f32[400,400]{1,0} convolution(x)", BATCH, VOCABS) == "other"
    assert classify("%a = (s32[]) while(x)", BATCH, VOCABS) is None


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_classes_and_gaps():
    ops = [
        op("f32[10131227,10]{0,1}", "fusion", 0, 40),       # table
        op("f32[8192,10]{1,0}", "gather", 30, 20, "g.2"),     # batch, overlaps
        op("(s32[])", "while", 0, 100, "while.3"),            # container
        op("f32[584]{0}", "fusion", 70, 10, "f.4"),           # small table
        op("f32[400,400]{1,0}", "dot", 95, 10, "dot.5"),      # clipped at 100
    ]
    spans = [Event("bench.next_chunk", 45e3, 30e3),
             Event("bench.dispatch", 80e3, 20e3),
             Event("python", 0, 100e3)]
    r = reduce(ops, spans, batch=BATCH, vocabs=VOCABS, window=(0, 100e3))
    # busy: [0, 50) + [70, 80) + [95, 100) us
    assert r["busy_s"] == pytest.approx(65e-6)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["class_s"]["table"] == pytest.approx(40e-6)
    assert r["class_s"]["batch"] == pytest.approx(20e-6)
    assert r["class_s"]["small_table"] == pytest.approx(10e-6)
    assert r["class_s"]["other"] == pytest.approx(5e-6)
    assert r["class_s"]["sort"] == 0
    # gaps [50, 70) under next_chunk, [80, 95) under dispatch
    assert dict(r["idle_gaps"]) == {
        "bench.next_chunk": pytest.approx(20e-6),
        "bench.dispatch": pytest.approx(15e-6)}
    assert r["device_ops"][0][1] == pytest.approx(40e-6)
    assert "[table]" in r["device_ops"][0][0]


def test_gap_without_a_span_is_named_so():
    ops = [op("f32[8]{0}", "fusion", 0, 10), op("f32[8]{0}", "fusion", 50, 10)]
    r = reduce(ops, [], batch=BATCH, vocabs=VOCABS, window=(0, 60e3))
    assert r["idle_gaps"] == [["no bench span", pytest.approx(40e-6)]]
