"""The round-span recorder (ISSUE 24): what one member round leaves in
the ring, that the timers the program had are now set from the spans'
own clock reads (equal integers), that the recorder is a pure observer
(protocol state and WAL bytes do not depend on it), that a profiler
session shows the spans in the xplane, and the engine's spans."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from etcd_tpu.batched import hosting, rawnode
from etcd_tpu.batched.hosting import GroupKV, InProcRouter, MultiRaftMember
from etcd_tpu.batched.rawnode import PHASES, STEP_PHASES
from etcd_tpu.obs import spans
from etcd_tpu.obs.export import chrome_trace, validate_chrome_trace
from etcd_tpu.pkg import metrics as pmet

# The size tests/benchmark's tiny cells use, so the member's round
# program is one this suite compiles anyway.
G = 8
MEMBERS = (1, 2, 3)


class SyncCluster:
    """Three members wired in one process and driven by hand, one round
    at a time on the calling thread (no member thread is started, the
    drain runs inline): the same calls give the same rounds."""

    def __init__(self, data_dir, trace=None):
        self.router = InProcRouter()
        self.members = {}
        for mid in MEMBERS:
            m = MultiRaftMember(mid, len(MEMBERS), G, str(data_dir),
                                pipeline=False, wal_pipeline=False,
                                trace=trace)
            self.router.attach(m)
            self.members[mid] = m

    def rounds(self, n):
        for _ in range(n):
            for m in self.members.values():
                m.run_round()

    def elect(self):
        for mid, m in self.members.items():
            m.campaign([g for g in range(G) if g % 3 == mid - 1])
        self.rounds(6)
        for mid, m in self.members.items():
            for g in range(G):
                assert m.rn.is_leader(g) == (g % 3 == mid - 1)

    def put_everywhere(self, tag):
        for mid, m in self.members.items():
            for g in range(G):
                if g % 3 == mid - 1:
                    assert m.propose(g, GroupKV.put_payload(
                        b"k%d-%d" % (g, tag), b"v%d" % tag))
        self.rounds(6)

    def stop(self):
        for m in self.members.values():
            m.stop()


@pytest.fixture
def cluster(tmp_path, monkeypatch):
    monkeypatch.setenv("ETCD_TPU_TRACE_SAMPLE", "1")  # every proposal
    c = SyncCluster(tmp_path, trace=True)
    yield c
    c.stop()


def mine(member, since):
    """This thread's spans of one member opened since ``since`` spans
    were in the ring."""
    tid = spans.DEFAULT.ring_id()
    return [s for s in spans.snapshot(member) if s.thread == tid][since:]


def count(member):
    return len(mine(member, 0))


class TestOneRound:
    def test_ring_order_parent_and_ids(self, cluster):
        cluster.elect()
        m = cluster.members[1]
        before = count(1)
        seq = m.stats["rounds"]
        m.propose(0, GroupKV.put_payload(b"a", b"b"))
        m.run_round()
        got = mine(1, before)
        names = [s.name for s in got]
        # Close order: the rawnode phases, then the round that holds
        # them, then the inline drain's spans.
        assert names[:len(PHASES) + 1] == [
            "rawnode." + p for p in PHASES] + ["member.round"]
        assert names[len(PHASES) + 1:] == [
            "member.fsync", "member.wal", "member.apply", "member.send"]
        assert all((s.member, s.round) == (1, seq) for s in got)
        by = {s.name: s for s in got}
        rnd = by["member.round"]
        for p in PHASES:
            s = by["rawnode." + p]
            assert s.parent == rnd.seq
            assert rnd.t0 <= s.t0 <= s.t1 <= rnd.t1
            # The thread's CPU clock is read for the phases a metric
            # reads it of, and for no other span.
            assert (s.cpu_ns >= 0) == (p in rawnode.CPU_PHASES)
        assert {s.cpu_ns for s in got
                if not s.name.startswith("rawnode.")} == {-1}
        assert rnd.parent == -1
        assert by["member.fsync"].parent == by["member.wal"].seq
        assert by["member.apply"].parent == by["member.send"].parent == -1
        assert by["member.wal"].stats == {"readys": 1}
        # Phases share their boundaries: no boundary is read twice.
        for a, b in zip(PHASES, PHASES[1:]):
            assert by["rawnode." + a].t1 == by["rawnode." + b].t0
        assert by["member.apply"].t1 == by["member.send"].t0
        assert set(rnd.stats) == set(hosting.SPAN_COUNTERS)

    def test_timers_are_set_from_the_spans(self, cluster):
        """phase_last, phase_total, member.stats and the tracer's
        stage / dispatch / extract / fsync / send / apply stamps are
        the spans' own integers."""
        cluster.elect()
        m = cluster.members[1]
        st0 = dict(m.stats)
        tot0 = dict(m.rn.phase_total)
        before = count(1)
        assert m.propose(0, GroupKV.put_payload(b"a", b"b"))
        m.run_round()
        by = {s.name: s for s in mine(1, before)}

        def ns(name):
            return by[name].t1 - by[name].t0

        pl = m.rn.phase_last
        assert pl["stage"] == ns("rawnode.stage") / 1e9
        assert pl["extract"] == ns("rawnode.extract") / 1e9
        assert pl["collect"] == ns("rawnode.collect") / 1e9
        step_ns = sum(ns("rawnode." + p) for p in STEP_PHASES)
        assert pl["step"] == step_ns / 1e9
        assert step_ns == (by["rawnode.extract"].t0
                           - by["rawnode.edits"].t0)
        tot = m.rn.phase_total
        for p in PHASES:
            assert tot[p] == tot0[p] + ns("rawnode." + p) / 1e9
        assert tot["step"] == tot0["step"] + step_ns / 1e9
        assert tot["rounds"] == tot0["rounds"] + 1
        for key, name in (("round_s", "member.round"),
                          ("wal_s", "member.wal"),
                          ("fsync_s", "member.fsync"),
                          ("apply_s", "member.apply"),
                          ("send_s", "member.send")):
            assert m.stats[key] == st0.get(key, 0.0) + ns(name) / 1e9, key
        assert m.stats["rounds"] == st0["rounds"] + 1
        assert m.stats["wal_fsyncs"] == st0.get("wal_fsyncs", 0) + 1

        # The entry persisted this round is traced (sample 1 of 1).
        frag = [sp for sp in m.tracer.spans()
                if sp["group"] == 0 and "stage" in sp["stages"]
                and sp["stages"]["stage"] == by["rawnode.stage"].t0]
        assert len(frag) == 1
        stamps = frag[0]["stages"]
        assert stamps["dispatch"] == by["rawnode.h2d"].t0
        assert stamps["extract"] == by["rawnode.extract"].t0
        assert stamps["fsync_wait"] == by["member.fsync"].t0
        assert stamps["fsync"] == by["member.fsync"].t1
        assert stamps["send"] == by["member.send"].t0

    def test_apply_stamp_is_the_end_of_member_apply(self, cluster):
        cluster.elect()
        cluster.put_everywhere(1)
        ends = {s.t1 for s in mine(1, 0) if s.name == "member.apply"}
        done = [sp for sp in cluster.members[1].tracer.spans()
                if "apply" in sp["stages"]]
        assert done
        assert all(sp["stages"]["apply"] in ends for sp in done)

    def test_counters(self, cluster):
        cluster.elect()
        cluster.put_everywhere(1)
        m = cluster.members[1]
        assert m.stats["leader_losses"] == 0
        # A ReadIndex batch opens and is confirmed by the quorum.
        m.rn.read_index(0)
        cluster.rounds(4)
        assert m.stats["read_opened"] == m.stats["read_confirmed"] == 1
        # Group 0 handed from member 1 to member 2: one row left LEADER.
        assert m.transfer_leader(0, 2)
        cluster.rounds(8)
        assert cluster.members[2].rn.is_leader(0)
        assert m.stats["leader_losses"] == 1
        last = [s for s in mine(1, 0) if s.name == "member.round"][-1]
        assert last.stats == {k: m.stats[k] for k in hosting.SPAN_COUNTERS}
        with pytest.raises(TimeoutError):
            m2 = cluster.members[2]
            m2.linearizable_get(0, b"k0-1", timeout=0.05)  # no round runs
        assert cluster.members[2].stats["read_timeouts"] == 1


class _Zero(dict):
    def __missing__(self, key):
        return 0


class _NullSpan:
    t0 = t1 = cpu_ns = 0
    seconds = 0.0
    stats = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


class _NullPhases(_NullSpan):
    def __init__(self):
        self.dur = _Zero()

    def next(self, name):
        return _NullSpan()

    def end(self):
        pass


def _run_schedule(data_dir):
    c = SyncCluster(data_dir)
    try:
        c.elect()
        for tag in range(3):
            c.put_everywhere(tag)
        state = {
            mid: {f: np.asarray(getattr(m.rn.state, f)).copy()
                  for f in m.rn.state._fields}
            for mid, m in c.members.items()}
        kvs = {mid: [dict(kv.data) for kv in m.kvs]
               for mid, m in c.members.items()}
    finally:
        c.stop()
    wal = {}
    for path in sorted(glob.glob(os.path.join(
            str(data_dir), "**", "wal", "*"), recursive=True)):
        with open(path, "rb") as f:
            wal[os.path.relpath(path, str(data_dir))] = f.read()
    return state, kvs, wal


def test_recorder_is_a_pure_observer(tmp_path, monkeypatch):
    """The same seeded schedule with the recorder, and with every span
    replaced by a stub that reads no clock and records nothing: device
    state, applied KV and WAL bytes are identical."""
    with_spans = _run_schedule(tmp_path / "a")
    for mod in (rawnode.spans, hosting.spans):
        monkeypatch.setattr(mod, "span", lambda *a, **k: _NullSpan())
        monkeypatch.setattr(mod, "phases", lambda *a, **k: _NullPhases())
        monkeypatch.setattr(mod, "record", lambda *a, **k: None)
    without = _run_schedule(tmp_path / "b")
    for mid in MEMBERS:
        for f, arr in with_spans[0][mid].items():
            assert np.array_equal(arr, without[0][mid][f]), (mid, f)
    assert with_spans[1] == without[1]
    assert with_spans[2] and with_spans[2] == without[2]


def test_profiler_session_shows_the_spans_beside_the_device(
        tmp_path, cluster):
    """Three rounds under an open profiler session (CPU backend): the
    rawnode.* and member.* events are in the xplane's host plane with
    their member and round as stats."""
    import jax
    from jax.profiler import ProfileData

    cluster.elect()
    m = cluster.members[1]
    first = m.stats["rounds"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "tr"), profiler_options=opts)
    try:
        cluster.rounds(3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(
        tmp_path / "tr" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    seen = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("rawnode.", "member.")):
                    st = dict(ev.stats)
                    seen.setdefault(ev.name, set()).add(
                        (st["member"], st["round"]))
    want = {(1, first + i) for i in range(3)}
    for p in PHASES:
        assert want <= seen["rawnode." + p]
    for name in ("member.round", "member.wal", "member.apply",
                 "member.send"):
        assert want <= seen[name]
    assert {mem for mem, _r in seen["member.round"]} == set(MEMBERS)


class TestRecorder:
    def test_overflow_is_counted_never_silent(self):
        """What a reading lost is counted as it reads: spans written
        since the last reading and pushed out before this one. A full
        ring that nobody reads counts nothing (the writer's path has no
        counter), and a span once read is never a drop."""
        from etcd_tpu.batched.telemetry import trace_drop_counter

        reg = pmet.Registry()
        rec = spans.Recorder(slots=8, registry=reg)
        child = trace_drop_counter(reg).labels("7", spans.DROP_CLASS)

        def write(lo, hi):
            for i in range(lo, hi):
                with rec.span("member.round", 7, i):
                    pass

        write(0, 20)
        assert rec.dropped() == 0 == child.value()  # nobody has read
        assert [s.round for s in rec.snapshot()] == list(range(12, 20))
        assert rec.dropped() == 12 == child.value()
        write(20, 26)  # pushes out six spans the reading above got
        assert [s.round for s in rec.snapshot()] == list(range(18, 26))
        assert rec.dropped() == 12 == child.value()
        write(26, 37)  # eleven since the last reading, eight kept
        assert rec.to_payload()["dropped"] == 15 == child.value()
        assert "etcd_tpu_trace_span_drops_total" in reg.expose()

    def test_cpu_time_is_read_for_the_named_phases_only(self):
        rec = spans.Recorder(slots=64, registry=pmet.Registry())
        with rec.span("member.round", 1, 0):
            with rec.phases("rawnode.", cpu=("stage", "collect")) as ph:
                for name in ("stage_lock", "stage", "edits", "extract",
                             "collect"):
                    ph.next(name)
                    sum(range(2000))
        got = {s.name: s.cpu_ns for s in rec.snapshot()}
        assert got["rawnode.stage"] > 0 and got["rawnode.collect"] > 0
        assert {got[n] for n in ("member.round", "rawnode.stage_lock",
                                 "rawnode.edits",
                                 "rawnode.extract")} == {-1}

    def test_children_take_the_parents_ids(self):
        rec = spans.Recorder(slots=64, registry=pmet.Registry())
        with rec.span("member.round", 2, 9) as outer:
            with rec.phases("rawnode.", 0, 123) as ph:
                a = ph.next("stage")
                b = ph.next("edits")
            with rec.span("member.fsync", 5, 5):
                pass
        got = {s.name: s for s in rec.snapshot()}
        assert {(s.member, s.round) for s in got.values()} == {(2, 9)}
        assert got["rawnode.stage"].t1 == got["rawnode.edits"].t0 == b.t0
        assert ph.dur == {"stage": a.t1 - a.t0, "edits": b.t1 - b.t0}
        assert all(s.parent == outer.seq for n, s in got.items()
                   if n != "member.round")
        assert outer.seconds == (outer.t1 - outer.t0) / 1e9

    def test_an_exception_leaves_the_stack_whole(self):
        rec = spans.Recorder(slots=64, registry=pmet.Registry())
        with pytest.raises(RuntimeError):
            with rec.span("member.round", 1, 1):
                rec.phases("rawnode.").next("stage")  # never closed
                raise RuntimeError("boom")
        with rec.span("member.round", 1, 2):
            pass
        last = rec.snapshot()[-1]
        assert (last.round, last.parent) == (2, -1)

    def test_one_ring_a_thread_and_a_cross_thread_record(self):
        rec = spans.Recorder(slots=64, registry=pmet.Registry())

        def work(member):
            for i in range(5):
                with rec.span("member.round", member, i):
                    pass

        ts = [threading.Thread(target=work, args=(mid,))
              for mid in MEMBERS]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
        rec.record("member.ready_q", 10, 25, 1, 3)
        got = rec.snapshot()
        assert len({s.thread for s in got}) == 4
        for mid in MEMBERS:
            assert [s.round for s in rec.snapshot(mid)
                    if s.name == "member.round"] == list(range(5))
        q = rec.snapshot(1)[-1]
        assert (q.name, q.t0, q.t1, q.cpu_ns, q.round) == (
            "member.ready_q", 10, 25, -1, 3)

    def test_dump_and_chrome_export(self, tmp_path):
        rec = spans.Recorder(slots=64, registry=pmet.Registry())
        with rec.span("member.round", 1, 0, readys=2):
            with rec.span("rawnode.stage"):
                pass
        path = rec.dump(member=1, reason="unit", dump_dir=str(tmp_path))
        assert os.path.basename(path).startswith("roundspans_m1_")
        with open(path) as f:
            payload = json.load(f)
        assert payload["fields"] == list(spans.SpanRec._fields)
        rows = [dict(zip(payload["fields"], r)) for r in payload["spans"]]
        assert [r["name"] for r in rows] == ["rawnode.stage",
                                             "member.round"]
        assert rows[1]["stats"] == {"readys": 2}
        # The admin 'trace' op's payload carries the ring as "rounds";
        # the merge renders it under the member.
        obj = chrome_trace([{"member": "1", "spans": [],
                             "rounds": payload}])
        slices = validate_chrome_trace(obj)
        assert {s["name"] for s in slices} == {"rawnode.stage",
                                               "member.round"}
        assert all(s["cat"] == "round" and s["pid"] == 1 for s in slices)


def test_drain_thread_spans_of_a_started_member(tmp_path):
    """With its threads running a member also records the idle wait of
    the round thread and, on the drain thread, the queue wait of every
    Ready under the round's own number."""
    from etcd_tpu.batched.hosting import MultiRaftCluster

    began = time.monotonic_ns()  # other tests' members share the ids
    c = MultiRaftCluster(str(tmp_path), num_members=3, num_groups=G)
    try:
        c.wait_leaders(timeout=120)
        c.put(0, b"k", b"v")
    finally:
        c.stop()
    for mid in MEMBERS:
        got = [s for s in spans.snapshot(mid) if s.t0 >= began]
        rounds = {s.round: s for s in got if s.name == "member.round"}
        waits = [s for s in got if s.name == "member.ready_q"]
        assert waits and any(s.name == "member.idle_wait" for s in got)
        for w in waits:
            assert w.t0 >= rounds[w.round].t1 and w.t1 >= w.t0
            assert w.thread != rounds[w.round].thread
        depth = max(s.stats["ready_q_depth_max"] for s in rounds.values())
        assert 1 <= depth <= 4


def test_engine_spans():
    """The engine at the benchmark's configuration cut to 8 groups (the
    programs tests/benchmark compiles anyway)."""
    import jax

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "benchmark", "configs",
                           "engine64k-r3.json")) as f:
        sizes = json.load(f)["sizes"]
    sizes["num_groups"] = G
    eng = MultiRaftEngine(BatchedConfig(**{
        k: sizes[k] for k in (
            "num_groups", "num_replicas", "window", "max_ents_per_msg",
            "max_props_per_round", "election_timeout",
            "heartbeat_timeout", "auto_compact", "lanes_minor",
            "deliver_shape")}))
    eng.campaign(np.arange(G) * 3)
    eng.run_rounds(4, tick=False)
    eng.run_rounds_pipelined(8, chunk=4, tick=False)
    jax.block_until_ready(eng.state.commit)
    assert (eng.leaders() == 0).all()
    got = [s for s in spans.snapshot(0)
           if s.name.startswith("engine.")
           and s.stats["engine"] == eng._serial]
    assert [(s.name, s.round) for s in got] == [
        ("engine.init", 0), ("engine.step_round", 1),
        ("engine.run_rounds", 2), ("engine.run_rounds", 3),
        ("engine.run_rounds", 4)]
    assert [s.stats.get("rounds") for s in got] == [None, None, 4, 4, 4]
    assert all(s.t1 >= s.t0 and s.parent == -1 for s in got)
