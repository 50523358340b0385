"""The trace reduction on a small trace recorded on a TPU v5e: the
serving engine of Qwen2-0.5B (48 slots) draining two requests, one
decode chunk and three span prefills, inside a ``chip_bench:`` window.
Reading it loads no TPU library."""

from __future__ import annotations

import shutil

import pytest

from chip_bench import trace
from chip_bench.tests.helpers import DATA

WINDOW_S = 1.768342576  # the recorded window span's duration


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    (d / "plugins" / "profile" / "0").mkdir(parents=True)
    shutil.copy(DATA / "serve_small.xplane.pb",
                d / "plugins" / "profile" / "0" / "host.xplane.pb")
    return trace.reduce(d, chips=1)


def _union_s(intervals):
    """Busy time by a sweep over the sorted boundaries."""
    events = sorted([(a, 1) for a, _ in intervals]
                    + [(b, -1) for _, b in intervals])
    depth, start, total = 0, 0, 0
    for t, d in events:
        if depth == 0 and d == 1:
            start = t
        depth += d
        if depth == 0:
            total += t - start
    return total / 1e9


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(WINDOW_S, abs=1e-9)
    assert 0 < summary.busy_s <= summary.window_s
    idle = sum(s for _, s in summary.gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s,
                                 abs=1e-6)


def _ops_in_window():
    """The first device's operation intervals, clipped to the window,
    read straight from the file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(DATA / "serve_small.xplane.pb"))
    window = ops = None
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace.WINDOW):
                    window = (e.start_ns, e.start_ns + e.duration_ns)
        if plane.name == "/device:TPU:0":
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == trace.OP_LINE
                   for e in line.events]
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in ops
            if min(b, hi) > max(a, lo)]


def test_busy_is_the_union_of_operations(summary):
    assert _union_s(_ops_in_window()) == pytest.approx(summary.busy_s,
                                                       abs=1e-9)


def test_programs_and_kernels(summary):
    chunk = summary.program_s("run_chunk")
    prefill = summary.program_s("prefill_span")
    assert chunk > prefill > 0
    decode = summary.kernel_s("paged_decode_attention")
    span = summary.kernel_s("paged_decode_span_attention")
    assert 0 < decode <= chunk and 0 < span <= prefill
    assert trace.program_name("jit_run_chunk(123)") == "run_chunk"
    assert trace.op_name("%paged_decode_attention.9 = f32[48,14,64] "
                         "custom-call(...)") == "paged_decode_attention"


def test_breakdown(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        rows = b[key]
        assert 0 < len(rows) <= trace.TOP
        assert [s for _, s in rows] == sorted((s for _, s in rows),
                                               reverse=True)
    assert not any(name.startswith("while") for name, _ in b["device_ops"])
    assert b["device_ops"][0][0].startswith("paged_decode_attention")


# A synthetic host timeline (ns): the window 0-1000 holds two steps, each
# train.step > train.batch, train.dispatch (> bench.call), train.sync;
# a checkpoint span runs past the window's end.
SPANS = [(0, 480, "train.step"), (0, 40, "train.batch"),
         (40, 200, "train.dispatch"), (60, 120, "bench.call"),
         (200, 480, "train.sync"),
         (500, 980, "train.step"), (500, 530, "train.batch"),
         (530, 700, "train.dispatch"), (700, 980, "train.sync"),
         (950, 1400, "ckpt.write"), (-300, -100, "train.batch")]


def test_program_spans_inside_the_window():
    got = trace.program_spans(SPANS, (0, 1000))
    assert set(got) == {"train.step", "train.batch", "train.dispatch",
                        "train.sync", "ckpt.write"}
    assert got["train.batch"] == [pytest.approx(70e-9), 2]
    assert got["train.step"] == [pytest.approx(960e-9), 2]
    assert got["ckpt.write"] == [pytest.approx(50e-9), 1]  # clipped


def test_idle_time_is_named_by_the_innermost_span():
    # device busy 100-300 and 380-940: idle 0-100 lies in train.step >
    # train.batch (0-40), > train.dispatch (40-60) and > bench.call
    # (60-100); 300-380 in train.step > train.sync; 940-1000 in
    # train.step > train.sync up to 980 (ckpt.write, longer, covers it
    # too), then in ckpt.write alone
    merged = [(100, 300), (380, 940)]
    got = trace._label_gaps(merged, 0, 1000, SPANS)
    assert got == [("train.batch", pytest.approx(40e-9)),
                   ("train.dispatch", pytest.approx(20e-9)),
                   ("bench.call", pytest.approx(40e-9)),
                   ("train.sync", pytest.approx(80e-9)),
                   ("train.sync", pytest.approx(40e-9)),
                   ("ckpt.write", pytest.approx(20e-9))]
    # between the two steps no span covers the host
    got = trace._label_gaps([(0, 490), (520, 1000)], 0, 1000, SPANS)
    assert got == [("host loop", pytest.approx(10e-9)),
                   ("train.batch", pytest.approx(20e-9))]
    assert trace._label_gaps([(0, 500)], 0, 1000, []) == \
        [("host loop", pytest.approx(500e-9))]


def test_chrome_spans_pair_begin_and_end():
    from repro.obs.trace import SpanTracer
    clock = iter(range(100)).__next__
    t = SpanTracer(clock=lambda: float(clock()))
    pid = t.process("train")
    with t.span("ckpt", pid=pid):
        with t.span("gather", pid=t.process("ckpt")):
            pass
    with t.span("ckpt", pid=pid):
        pass
    assert trace.chrome_processes(t.events) == {0: "train", 1: "ckpt"}
    got = trace.chrome_spans(t.events)
    # clock ticks: train.ckpt 0-3 and 4-5, ckpt.gather 1-2
    assert got == {"train.ckpt": [pytest.approx(4.0), 2],
                   "ckpt.gather": [pytest.approx(1.0), 1]}
