"""The program's spans beside the trace record (``bench/spans.py``), the
span report and the readers of ``queue.admit_ms`` and ``sweep.idle_ms``:
synthetic records with answers worked out by hand, and the spans of a
short profiler trace taken on the CPU."""
from pathlib import Path

import pytest

from bench import harness
from bench import spans as sp

ROOT = Path(__file__).resolve().parents[2]


def _rec():
    # window 0..100 ns; chip 0 busy 0..10 and 70..80, so idle 10..70 and
    # 80..100. A batch's sweep (20..60) nests in a wait on one thread
    # (0..100); another thread's admission (15..30) overlaps both
    return {
        "window": [0.0, 100.0],
        "device": [[0, "%fusion.1 = f64[8] fusion(...)", 0.0, 10.0],
                   [0, "%fusion.2 = f64[8] fusion(...)", 70.0, 10.0]],
        "programs": [[0, "jit_f(1)", 0.0, 80.0]],
        "host": [["sweep", 20.0, 60.0]],
        "spans": [["queue.flush_wait", 0.0, 100.0, 1, {"batch": 0}],
                  ["queue.admit", 15.0, 30.0, 0, {"priority": 0}],
                  ["pipeline.sweep", 20.0, 60.0, 1, {"run": 0, "batch": 0}]],
        "op_scopes": {"%fusion.1 = f64[8] fusion(...)": ["hits.hub", "tf_op"]},
        "op_stats": [["tf_op", "jit(f)/hits.hub/mul"]],
    }


def test_idle_inside_spans_counts_each_instant_once():
    rec = _rec()
    assert sp.idle_in(rec, [(20.0, 60.0)]) == 40.0
    # overlapping intervals are merged; busy time inside them is not idle
    assert sp.idle_in(rec, [(5.0, 30.0), (25.0, 75.0), (78.0, 90.0)]) == 70.0
    assert sp.idle_in(rec, []) == 0.0


def test_idle_by_span_goes_to_the_innermost_span_first():
    rec = _rec()
    got = dict(sp.idle_by_span(rec, rec["spans"]))
    # admission (15 ns long) takes 15..30 first, the sweep (40 ns) the
    # rest of 20..60, the wait (100 ns) 10..15, 60..70 and 80..100
    assert got == pytest.approx({"queue.admit": 15e-9,
                                 "pipeline.sweep": 30e-9,
                                 "queue.flush_wait": 35e-9})
    assert sum(got.values()) == pytest.approx(80e-9)  # all the idle time


def test_idle_no_span_covers_is_named_as_such():
    rec = _rec()
    got = dict(sp.idle_by_span(rec, [["engine.sync", 40.0, 50.0, 0, {}]]))
    assert got == pytest.approx({"engine.sync": 10e-9,
                                 sp.NO_SPAN: 70e-9})
    assert sp.idle_by_span(rec, []) == [[sp.NO_SPAN, pytest.approx(80e-9)]]
    # the uncovered stretches, longest first, from the window's start
    got = sp.uncovered(rec, [["engine.sync", 40.0, 50.0, 0, {}]])
    assert [x for pair in got for x in pair] == pytest.approx(
        [10e-9, 30e-9, 50e-9, 20e-9, 80e-9, 20e-9])


def test_scope_of_reads_the_named_scopes_from_an_op_stat():
    stats = [("program_id", 5),
             ("tf_op", "jit(_converge_batch)/while/body/hits.authority/"
                       "scatter-add")]
    assert sp.scope_of(stats) == ["hits.authority", "tf_op"]
    nested = [("long_name", "jit(_converge_batch)/hits.certificate/"
                            "hits.hub/mul")]
    assert sp.scope_of(nested) == ["hits.certificate/hits.hub", "long_name"]
    assert sp.scope_of([("hlo_op", "fusion.3")]) == ["", ""]


def test_device_scopes_sum_leaf_time_by_scope():
    got = dict(sp.device_scopes(_rec(), _rec()["op_scopes"]))
    assert got == pytest.approx({"hits.hub": 10e-9, "unscoped": 10e-9})


def test_span_ms_and_window():
    rec = _rec()
    spans = rec["spans"] + [["engine.build", -50.0, -10.0, 0, {"job": 0}]]
    inside = sp.in_window(rec, spans)
    assert [s[0] for s in inside] == ["queue.flush_wait", "queue.admit",
                                      "pipeline.sweep"]
    assert sp.span_ms(inside)["pipeline.sweep"] == [1, pytest.approx(4e-5)]


def test_span_report_on_a_record():
    from bench import span_report
    out = span_report.report(_rec())
    assert out["idle_in_span_ms"]["pipeline.sweep"] == pytest.approx(4e-5)
    assert out["scope_stats"] == ["tf_op"]
    assert dict(out["idle_by_span"])["queue.admit"] == pytest.approx(15e-9)


def test_read_takes_program_spans_with_their_ids(tmp_path):
    import jax

    from repro.serve.telemetry import span
    with jax.profiler.trace(str(tmp_path)):
        with span("engine.sweep", job=3, sweep=1):
            with span("engine.sync"):
                jax.numpy.ones(4).block_until_ready()
        with span("not.a.program.span"):
            pass
    got = sp.read(str(tmp_path))
    names = [s[0] for s in got["spans"]]
    assert names == ["engine.sweep", "engine.sync"]
    outer, inner = got["spans"]
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert outer[3] == inner[3]  # one thread
    assert outer[4] == inner[4] == {"job": 3, "sweep": 1}
    assert got["op_scopes"] == {} and got["op_stats"] == []  # no TPU plane


@pytest.mark.parametrize("metric", ["queue.admit_ms", "sweep.idle_ms"])
def test_new_readers_find_nothing_untraced_or_in_an_older_program(metric):
    reader = harness.load_reader(ROOT, metric)
    assert reader.read({"trace": None, "svc_delta": {}, "queue_delta": {}}) \
        is None
    # a program without the admission histogram, a trace without sweeps
    assert reader.read({"trace": dict(_rec(), host=[]),
                        "svc_delta": {("pipeline.swept", None): 2},
                        "queue_delta": {("queue.wait_ms", None): (3, 9.0)}}
                       ) is None


def test_new_readers_read_their_sources():
    admit = harness.load_reader(ROOT, "queue.admit_ms")
    assert admit.read({"queue_delta": {("queue.admit_ms", None): (4, 10.0)}}
                      ) == pytest.approx(2.5)
    idle = harness.load_reader(ROOT, "sweep.idle_ms")
    # 40 ns idle inside the one sweep, over 2 batches swept, in ms
    assert idle.read({"trace": _rec(),
                      "svc_delta": {("pipeline.swept", None): 2}}
                     ) == pytest.approx(2e-5)


def _pb(*fields):
    """A protobuf message from ``(field, value)``: an int is a varint,
    bytes or str a length-delimited field."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_device_op_scopes_come_from_the_event_metadata():
    # an XSpace with a host plane and a TPU plane whose op metadata keeps
    # the HLO op name in a ``tf_op`` stat, once as a string and once as a
    # reference to an interned string
    stat_meta = [_pb((1, k), (2, _pb((1, k), (2, name))))
                 for k, name in ((7, "tf_op"),
                                 (8, "jit(f)/while/body/hits.authority/add"))]
    ops = [_pb((1, 3), (2, _pb((1, 3), (2, "fusion.1"),
                               (4, "%fusion.1 = f64[8] fusion(...)"),
                               (5, _pb((1, 7), (5, "jit(f)/hits.hub/mul")))))),
           _pb((1, 4), (2, _pb((1, 4), (2, "fusion.2"),
                               (5, _pb((1, 7), (7, 8)))))),
           _pb((1, 5), (2, _pb((1, 5), (2, "copy.3"))))]
    tpu = _pb((1, 1), (2, "/device:TPU:0"), (3, _pb((2, "XLA Ops"))),
              *[(5, m) for m in stat_meta], *[(4, m) for m in ops])
    host = _pb((2, "/host:CPU"), (4, ops[0]))
    scopes, sample = sp._device_op_scopes(_pb((1, host), (1, tpu)))
    assert scopes == {"fusion.1": ["hits.hub", "tf_op"],
                      "%fusion.1 = f64[8] fusion(...)": ["hits.hub", "tf_op"],
                      "fusion.2": ["hits.authority", "tf_op"],
                      "copy.3": ["", ""]}
    assert sample[0][0] == "tf_op"
