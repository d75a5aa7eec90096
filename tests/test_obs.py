"""repro.obs, the program's span recorder: parent ids, threads, the ring,
windows, and the spans' place in a profiler trace."""
import glob
import threading

import jax
import jax.numpy as jnp

from repro import obs


def test_nested_spans_name_their_parents():
    rec = obs.Recorder()
    with rec.span("outer") as outer:
        with rec.span("inner") as inner:
            assert inner.parent == outer.id
        with rec.span("inner") as second:
            pass
    assert outer.parent == obs.NO_PARENT
    got = rec.spans("inner")
    assert list(got["id"]) == [inner.id, second.id]
    assert list(got["parent"]) == [outer.id, outer.id]
    (o,) = rec.spans("outer")
    assert o["id"] == outer.id and o["start"] <= got["start"].min()
    assert got["end"].max() <= o["end"]


def test_exception_still_closes_the_span():
    rec = obs.Recorder()
    try:
        with rec.span("boom"):
            raise KeyError("x")
    except KeyError:
        pass
    assert len(rec.spans("boom")) == 1
    with rec.span("after") as after:
        pass
    assert after.parent == obs.NO_PARENT       # the stack was unwound


def test_threads_record_at_once_without_loss():
    rec = obs.Recorder()
    n_threads, per = 8, 2000
    ids = [[] for _ in range(n_threads)]
    go = threading.Barrier(n_threads)

    def worker(k):
        go.wait()
        for _ in range(per):
            with rec.span("t") as outer:
                with rec.span("t.child") as child:
                    assert child.parent == outer.id
            ids[k].append(outer.id)
            rec.record("t.cross", 0.0, 1.0, obs.next_id(), outer.id)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    outer = rec.spans("t")
    assert len(outer) == n_threads * per
    assert len(set(outer["id"])) == n_threads * per
    assert set(outer["id"]) == {i for k in ids for i in k}
    child = rec.spans("t.child")
    assert set(child["parent"]) == set(outer["id"])
    assert len(rec.spans("t.cross")) == n_threads * per
    assert rec.dropped("t") == 0


def test_ring_wraps_and_counts_what_it_overwrote():
    rec = obs.Recorder(capacity=8)
    for i in range(1, 21):
        rec.record("r", float(i), float(i) + 0.5, i)
    got = rec.spans("r")
    assert list(got["id"]) == list(range(13, 21))     # the newest, in order
    assert rec.dropped("r") == 12
    assert rec.dropped("never") == 0
    assert len(rec.spans("never")) == 0
    assert rec.spans("never").dtype == obs.RECORD


def test_record_many_writes_a_batch_and_wraps():
    rec = obs.Recorder(capacity=8)
    rec.record("b", 0.5, 0.75, 1)
    rec.record_many("b", [1.0, 2.0, 3.0], 3.5, [2, 3, 4], parent=9)
    got = rec.spans("b")
    assert list(got["id"]) == [1, 2, 3, 4]
    assert list(got["parent"]) == [obs.NO_PARENT, 9, 9, 9]
    assert list(got["start"]) == [0.5, 1.0, 2.0, 3.0]
    assert list(got["end"]) == [0.75, 3.5, 3.5, 3.5]
    rec.record_many("b", range(10, 16), 20.0, range(5, 11), 7)   # wraps
    got = rec.spans("b")
    assert list(got["id"]) == list(range(3, 11)) and rec.dropped("b") == 2
    assert list(got["start"]) == [2.0, 3.0] + list(range(10, 16))
    assert list(got["parent"]) == [9, 9] + [7] * 6
    rec.record_many("b", [], 30.0, [])
    assert rec.dropped("b") == 2 and len(rec.spans("b")) == 8
    rec.record_many("b", range(100, 120), 200.0, range(100, 120))
    got = rec.spans("b")                 # longer than the ring: the newest
    assert list(got["id"]) == list(range(112, 120))
    assert rec.dropped("b") == 22


def test_window_keeps_records_wholly_inside():
    rec = obs.Recorder()
    for a, b, i in [(1.0, 2.0, 1), (2.5, 3.5, 2), (3.0, 5.0, 3),
                    (0.5, 1.5, 4), (4.0, 4.5, 5)]:
        rec.record("w", a, b, i)
    assert list(rec.spans("w", 1.0, 4.5)["id"]) == [1, 2, 5]
    assert list(rec.spans("w", start=2.0)["id"]) == [2, 3, 5]
    assert list(rec.spans("w", end=3.5)["id"]) == [1, 2, 4]
    assert len(rec.spans("w", 10.0, 11.0)) == 0


def test_module_recorder_uses_one_clock_and_one_id_counter():
    a, b = obs.next_id(), obs.next_id()
    assert b > a > 0
    t0 = obs.clock()
    with obs.span("obs.test.module") as s:
        pass
    (r,) = obs.spans("obs.test.module", t0, obs.clock())
    assert r["id"] == s.id > b


def test_spans_land_in_the_profiler_trace(tmp_path):
    """Under a running profiler each span is an event of its name on a
    host plane, inside the annotation that encloses it."""
    from jax.profiler import ProfileData
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("obs.test.outer"):
            with obs.span("obs.test.span"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    found = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ("obs.test.outer", "obs.test.span"):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {"obs.test.outer", "obs.test.span"}
    (o0, o1), (s0, s1) = found["obs.test.outer"], found["obs.test.span"]
    assert o0 <= s0 < s1 <= o1
