"""The reduction from a device trace to busy time, idle share, kernel
time and named idle gaps, on a synthetic trace."""
import pytest

import _bench_path  # noqa: F401
from harness import profile as P

MS = 1e6


def _trace():
    # window 0..100 ms; ops overlap at 10-30 and 25-40, a kernel 60-70
    ops = [(10 * MS, 20 * MS, "fusion.1"), (25 * MS, 15 * MS, "fusion.2"),
           (60 * MS, 10 * MS, "alpha_combine"), (95 * MS, 20 * MS,
                                                 "fusion.1")]
    spans = [(0.0, 100 * MS, P.WINDOW_SPAN),
             (0.0, 55 * MS, P.ROUND_SPAN + " 7"),
             (55 * MS, 45 * MS, P.ROUND_SPAN + " 8"),
             (40 * MS, 18 * MS, "bench.solve"),
             (38 * MS, 21 * MS, "sim.solve"),
             (0.0, 9 * MS, "sim.restack"),
             (72 * MS, 20 * MS, "bench.log")]
    return ops, spans


def test_op_names_are_the_instructions():
    text = ("%alpha_combine_flat.1 = f32[128,49152]{1,0} custom-call("
            "%copy-done, %pad.0), custom_call_target=\"tpu_custom_call\"")
    assert P.op_name(text) == "alpha_combine_flat.1"
    assert P.op_name("%slice.5 = f32[128,48158] slice(f32[128,49152] "
                     "%alpha_combine_flat.1)") == "slice.5"


def test_union_merges_overlaps():
    assert P.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_busy_and_idle_share_clip_to_window():
    ops, spans = _trace()
    s = P.summarize([ops], spans)
    # 10-40, 60-70, 95-100 (the last op is cut at the window's end)
    assert s.busy_ns == pytest.approx(45 * MS)
    assert s.window_ns == pytest.approx(100 * MS)
    assert s.idle_share == pytest.approx(0.55)


def test_busy_is_averaged_over_chips():
    ops, spans = _trace()
    s = P.summarize([ops, [(0.0, 100 * MS, "fusion.9")]], spans)
    assert s.busy_ns == pytest.approx((45 + 100) / 2 * MS)


def test_kernel_time_and_count():
    ops, spans = _trace()
    s = P.summarize([ops], spans)
    ns, n = s.time_of(lambda name: "alpha_combine" in name)
    assert (ns, n) == (pytest.approx(10 * MS), 1)


def test_idle_gaps_are_named_by_the_host_span():
    """By the innermost span, the harness's or the program's: the gap in
    a restack the harness does not wrap is the program's ``sim.restack``,
    the gap in a solve the harness's ``bench.solve`` inside ``sim.solve``."""
    ops, spans = _trace()
    b = P.breakdown(P.summarize([ops], spans))
    gaps = dict((name, sec) for name, sec in b["idle_gaps"])
    assert set(gaps) == {"bench.log (round 8)", "sim.restack (round 7)",
                         "bench.solve (round 7)"}
    assert gaps["bench.log (round 8)"] == pytest.approx(0.025)
    assert gaps["sim.restack (round 7)"] == pytest.approx(0.010)
    assert gaps["bench.solve (round 7)"] == pytest.approx(0.020)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]


def test_a_trace_without_the_window_span_is_refused():
    ops, _ = _trace()
    with pytest.raises(ValueError):
        P.summarize([ops], [])


def test_load_keeps_the_harness_and_program_spans(tmp_path):
    """A trace recorded on the host: the harness's ``bench.*`` and the
    program's ``sim.*`` annotations are kept, others are not."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in (P.WINDOW_SPAN, "sim.restack", "other.span"):
            with jax.profiler.TraceAnnotation(name):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    _, spans = P.load(str(tmp_path))
    names = {name for _, _, name in spans}
    assert {P.WINDOW_SPAN, "sim.restack"} <= names
    assert "other.span" not in names
