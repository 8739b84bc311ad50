"""The reduction from a trace to busy time, idle share, top ops and
labelled idle gaps, on small hand-made traces; and the extraction from a
trace the profiler really wrote."""
import pytest

from bench import trace_reduce, tracing

MS = 1_000_000      # ns


def _trace():
    # a 10 ms call: dispatch for 2 ms, then waiting for the device; a
    # while op spans its loop, its body's ops run inside it
    host = [("call", 0, 10 * MS), ("dispatch", 0, 2 * MS),
            ("wait", 2 * MS, 8 * MS)]
    ops = [("%while.7 = (f32[]) while(...)", 1 * MS, 8 * MS),
           ("%fusion.1 = f32[] fusion(...)", 1 * MS, 2 * MS),
           ("%fusion.2 = f32[] fusion(...)", 2 * MS, 2 * MS),
           ("%fusion.3 = f32[] fusion(...)", 5 * MS, 1 * MS),
           ("%fusion.1 = f32[] fusion(...)", 8 * MS, 1 * MS),
           ("%copy.9 = f32[] copy(...)", 9 * MS, 3 * MS)]  # past the call
    return {"device": {"/device:TPU:0": ops}, "host": host}


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 6)]


def test_leaves_drop_the_ops_that_enclose_others():
    names = [trace_reduce.op_name(n)
             for n, _, _ in trace_reduce.leaves(_trace()["device"][
                 "/device:TPU:0"])]
    assert "while.7" not in names
    assert sorted(names) == ["copy.9", "fusion.1", "fusion.1", "fusion.2",
                             "fusion.3"]


def test_busy_idle_and_window():
    r = trace_reduce.reduce(_trace())
    assert r["window_s"] == pytest.approx(10e-3)
    # leaf busy: [1,4) + [5,6) + [8,10) clipped to the call = 6 ms
    assert r["busy_s"] == pytest.approx(6e-3)
    assert r["ops"][0] == ["fusion.1", pytest.approx(3e-3)]
    assert dict(r["ops"])["copy.9"] == pytest.approx(1e-3)


def test_gaps_labelled_by_host_span():
    r = trace_reduce.reduce(_trace())
    # gaps: [0,1) in dispatch, [4,5) and [6,8) in wait
    assert r["gaps"] == [["wait", pytest.approx(2e-3)],
                         ["dispatch", pytest.approx(1e-3)],
                         ["wait", pytest.approx(1e-3)]]


def test_busy_is_averaged_over_devices():
    tr = _trace()
    tr["device"]["/device:TPU:1"] = [("%f = f()", 0, 10 * MS)]
    assert trace_reduce.reduce(tr)["busy_s"] == pytest.approx(8e-3)


def test_no_device_ops_is_an_error():
    tr = _trace()
    tr["device"] = {}
    with pytest.raises(ValueError, match="no device ops"):
        trace_reduce.reduce(tr)


def test_extract_reads_the_host_spans_of_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    out, path = tracing.capture(lambda: f(x), tmp_path / "trace")
    tr = tracing.extract(path)
    assert [n for n, _, _ in tr["host"]] == ["call", "dispatch", "wait"]
    (call, c0, cd), (_, d0, _), (_, w0, wd) = tr["host"]
    assert c0 <= d0 < w0 and w0 + wd <= c0 + cd
    assert tr["program"] == []        # the program wrote no spans
    assert out.shape == (64, 64)
