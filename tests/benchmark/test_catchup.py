"""The deep-log cell's own pieces on the CPU (ISSUE 50):
``engine100k-r3-deeplog.reboot-catchup`` as the issue states it and as
the contract's rules admit it, ``catchup_checks.py``'s comparisons each
shown to fail on a fault handed to it, the run table's numpy reading
against a plain list, each new reader on a made context, and the cell
driven tiny (its period cut to 512 rounds, the sizes but the groups its
own): ``correct`` true, both controls false."""

import json
import os
import tempfile
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.catchup_checks import (LEADER, REPLICATE, engine_checks,
                                      group_checks, level_checks, log_terms,
                                      run_checks, runs_term_at)
from benchmark.compare import verdict
from benchmark.drivers import engine_catchup
from benchmark.generators import engine_faults_rounds as gen
from benchmark.readers import catchup as reader

from .util import (REPO, SHARED_AT_52, TINY_DIR, UNLISTED, bench, cell_root,
                   own_entries, reaches, shared_with)

CONFIG = "engine100k-r3-deeplog"
CELL = CONFIG + ".reboot-catchup"
with open(os.path.join(TINY_DIR, CELL + ".json")) as _f:
    TINY = json.load(_f)["traffic"]  # a period of 512 rounds
NEW = ["round.log_pct", "catchup.rounds_to_level",
       "catchup.rejects_per_return", "catchup.snapshots_per_return",
       "catchup.ents_per_app", "log.depth_entries"]


def load_json(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# -- the entries --------------------------------------------------------------------


def test_the_configuration_is_the_issues():
    cfg = load_json("configs", CONFIG)
    assert cfg["sizes"] == {
        "num_groups": 102400, "num_replicas": 3, "window": 10240,
        "max_ents_per_msg": 64, "max_props_per_round": 2,
        "election_timeout": 10, "heartbeat_timeout": 1,
        "max_inflight": 512, "pre_vote": True, "check_quorum": True,
        "auto_compact": True, "lanes_minor": True, "deliver_shape": "auto",
        "telemetry": True, "log_runs": 32}
    assert cfg["sizes"]["window"] // 2 >= 5000
    assert (cfg["driver"], cfg["reduced"]) == ("engine_catchup", [])
    old = load_json("configs", "engine100k-r3")
    assert cfg["guarantees"][:6] == old["guarantees"]
    assert len(cfg["guarantees"]) == 9
    assert set(cfg["assumed"]) >= {
        "max_ents_per_msg", "max_inflight", "window", "log_runs", "round",
        "randomized_timeout", "fault_duty_cycle"}
    assert "never binds" in cfg["assumed"]["max_inflight"]
    entry = [c for c in bench()["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("server.go:80", "5000", "bootstrap.go:523-536", "512",
                 "BLACKHOLE_PEER_PORT_TX_RX_ONE_FOLLOWER"):
        assert word in cfg["source"], word


def test_the_traffic_is_the_issues():
    t = load_json("traffic", "reboot-catchup")
    assert t == {
        "name": "reboot-catchup", "generator": "engine_faults_rounds",
        "loop": "closed", "proposals_per_round": 2, "rounds_per_call": 64,
        "tick": True, "trace_calls": 1, "period_rounds": 4096,
        "cut_from_round": 1024, "cut_rounds": 2048, "level_rounds": 128}
    load = gen.make(t, {"num_groups": 8, "num_replicas": 3}, 2**31 + 9)
    k0 = load["first_cut_node"]
    assert gen.cut_node(load, 1023) is None
    assert gen.cut_node(load, 1024) == k0 == gen.cut_node(load, 3071)
    assert gen.cut_node(load, 3072) is None
    assert gen.cut_node(load, 4096 + 1024) == (k0 + 1) % 3
    # Away for 2,048 rounds of 2 entries: 4,096 behind of 5,120 kept.
    assert t["cut_rounds"] * t["proposals_per_round"] == 4096 < 5120


def follows_rule(b: dict) -> None:
    """Appended, by rule: everything PR 47's file had stands first and
    in its order; one four-chip cell still."""
    before = ["engine64k-r3", "engine10k-r5", "engine100k-r3", "engine1m-r3",
              "engine512k-r3of4", "engine1m-r3of4-x4",
              "engine768k-r3of4-rebalance", "engine1m-r3-zipf"]
    names = [c["name"] for c in b["configs"]]
    assert names[:8] == before and names.index(CONFIG) == 8
    cells = [w["name"] for w in b["workloads"]]
    assert [w["config"] for w in b["workloads"]][:8] == before
    assert cells.index(CELL) == 8 and cells.count(CELL) == 1
    cell = b["workloads"][8]
    assert cell == dict(cell, config=CONFIG, traffic="reboot-catchup",
                        chips=1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200
    rate = b["end_to_end"][0]
    assert (rate["name"], rate["bound"]) == ("group_rounds_per_s", 0.01)
    assert rate["workloads"][:9] == cells[:9]
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] == [
        "engine1m-r3of4-x4.replace-readindex-x4"]
    assert b["run_seconds"] == 30


def test_the_cell_follows_what_was_there():
    follows_rule(bench())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 << 10


def entries_rule(b: dict) -> None:
    """The cell's six stand right after PR 47's six (PR 50 wrote them
    and had to park them behind a pin; PR 52 pasted them here), in
    their order, for this cell alone; what follows them is a later
    PR's."""
    for m in own_entries(b, NEW, 60, CELL):
        assert m["moves"] == "group_rounds_per_s"
        spec = load_json("layer_metrics", m["name"])
        mod = spec["reader"].partition(".")[0]
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "readers", mod + ".py"))


def test_the_new_entries_list_this_cell_alone_and_are_live():
    b = bench()
    entries_rule(b)
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "parked", "catchup.json"))
    assert not hasattr(engine_catchup.Driver, "say_layers")
    log = load_json("layer_metrics", "round.log_pct")
    assert (log["reader"], log["params"]) == (
        "trace.scope_pct", {"scope": "raft_log"})
    got = {m["name"]: (m["unit"], m["better"], m["source"], m["layer"])
           for m in b["per_layer"][60:66]}
    assert got == {
        "round.log_pct": ("%", "lower", "device_trace", "round program"),
        "catchup.rounds_to_level": ("rounds", "lower", "program_counter",
                                    "closed-loop engine"),
        "catchup.rejects_per_return": ("per_return", "lower",
                                       "program_counter", "telemetry plane"),
        "catchup.snapshots_per_return": ("per_return", "lower",
                                         "program_counter",
                                         "telemetry plane"),
        "catchup.ents_per_app": ("entries", "higher", "program_counter",
                                 "closed-loop engine"),
        "log.depth_entries": ("entries", "higher", "program_counter",
                              "closed-loop engine")}


def test_the_cell_resolves_to_its_files():
    b = bench()
    cell = harness.Cell(REPO, CELL)
    assert cell.config["name"] == CONFIG and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == [
        "group_rounds_per_s", "setup_s"]
    mine = {m["name"] for m in cell.per_layer}
    # The accepted entries that name no cell reach this one, its own
    # six, and the shared ones that list it: of the round's and the
    # scan's layers all but the tiles' (one scan over all rows), no
    # read's (none is asked), and alone of all cells the bulk half's.
    assert UNLISTED <= mine == reaches(b, CELL)
    # (At least: a later PR may bring one more view of this cell.)
    assert mine >= {m["name"] for m in b["per_layer"]
                    if "workloads" not in m} | set(NEW) | shared_with(
                        b, CELL)
    assert set(SHARED_AT_52) - mine == {
        "scan.tiles_pct", "setup.pretrace_s", "read.confirmed_per_kgr",
        "read.rounds_to_confirm"}
    assert [m["workloads"][0] for m in b["per_layer"]
            if m["name"] == "round.bulk_pct"] == [CELL]
    assert cell.module("drivers", "engine_catchup") is engine_catchup


# -- the run table read in numpy -----------------------------------------------------

G, R, K = 6, 3, 8
KEPT = 40


def test_the_numpy_reading_of_a_run_table_is_a_plain_lists():
    rng = np.random.default_rng(50)
    for _ in range(50):
        terms = np.sort(rng.integers(1, 6, size=int(rng.integers(1, 60))))
        first = int(rng.integers(1, 100))
        runs = np.zeros((2, K), np.int32)
        for t in np.unique(terms):
            runs[:, t % K] = (first + int(np.argmax(terms == t)), t)
        # A run left below the floor, as the device leaves it.
        runs[:, 7] = (max(first - 3, 0), 0 if first < 4 else terms[0])
        snap = first - 1 + int(rng.integers(0, len(terms)))
        last = first + len(terms) - 1
        want = [(first + j, int(t)) for j, t in enumerate(terms)
                if first + j > snap]
        assert log_terms(runs, snap, last) == want
    idx = np.array([[3, 4, 9]])
    table = np.array([[[4, 9, 0, 0], [2, 5, 0, 0]]])
    assert runs_term_at(table, idx).tolist() == [[0, 2, 5]]


def sound_state():
    """Six groups as the cell ends a period: one leader, replicas
    agreed, every log the same two runs (term 2 from 101, term 3 from
    150) above a floor KEPT below its last index."""
    n = G * R
    st = {f: np.zeros(n, np.int32) for f in
          ("term", "role", "lead", "commit", "last", "snap_index")}
    st["log_term"] = np.zeros((n, 2, K), np.int32)
    for g in range(G):
        lead = g % R
        for s in range(R):
            i = g * R + s
            st["term"][i] = 3
            st["role"][i] = LEADER if s == lead else 0
            st["lead"][i] = lead + 1
            st["commit"][i] = 180 + g - (0 if s == lead else 2)
            st["last"][i] = st["commit"][i] + 4
            st["snap_index"][i] = st["last"][i] - KEPT
            st["log_term"][i, :, 2] = (101, 2)
            st["log_term"][i, :, 3] = (150, 3)
    return st


def test_sound_state_passes_the_group_checks():
    checks = group_checks(sound_state(), G, R, KEPT)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert len(checks) == 5 and all(c.limit == 0 for c in checks)


def two_leaders_in_a_term(st):
    st["role"][1 * R + 2] = LEADER
    return "groups_with_two_leaders_in_a_term"


def no_leader(st):
    st["role"][2 * R:3 * R] = 0
    return "groups_without_exactly_one_leader"


def replicas_disagree_on_the_leader(st):
    st["lead"][4 * R + 1] = 3
    return "groups_disagreeing_on_term_or_leader"


def a_committed_run_that_starts_elsewhere(st):
    st["log_term"][5 * R + 1, 0, 3] = 152  # entries 150, 151 of term 2
    return "groups_whose_committed_prefixes_differ"


def a_committed_run_of_another_term(st):
    st["log_term"][3 * R + 2, :, 3] = 0
    st["log_term"][3 * R + 2, :, 4] = (150, 4)
    return "groups_whose_committed_prefixes_differ"


def a_replica_past_the_entries_kept_behind(st):
    st["commit"][0 * R + 1] = 180 - KEPT - 1
    return "replicas_lagging_their_leader_past_the_entries_kept"


@pytest.mark.parametrize("fault", [
    two_leaders_in_a_term, no_leader, replicas_disagree_on_the_leader,
    a_committed_run_that_starts_elsewhere, a_committed_run_of_another_term,
    a_replica_past_the_entries_kept_behind], ids=lambda f: f.__name__)
def test_group_fault_is_not_correct(fault):
    st = sound_state()
    name = fault(st)
    bad = {c.name for c in group_checks(st, G, R, KEPT) if not c.ok}
    assert name in bad


def test_a_run_that_differs_above_the_commit_is_no_fault():
    """An uncommitted suffix may differ: log matching is held over the
    committed prefix both replicas hold."""
    st = sound_state()
    i = 2 * R + 1
    st["log_term"][i, :, 4] = (int(st["commit"][i]) + 3, 4)
    assert verdict(group_checks(st, G, R, KEPT))


@pytest.mark.parametrize("name", ["sent_snapshot", "to_snapshot"])
def test_a_snapshot_anywhere_in_the_run_is_not_correct(name):
    totals = {"sent_snapshot": 0, "to_snapshot": 0, "sent_append": 9}
    assert verdict(run_checks(totals))
    totals[name] = 1
    checks = run_checks(totals)
    assert [c.name for c in checks if not c.ok] == ["run_with_" + name]


def level_state(node=1):
    role = np.zeros(G * R, np.int32)
    commit = np.zeros(G * R, np.int32)
    pr = np.zeros((G * R, R), np.int32)
    for g in range(G):
        lead = (node + 1 + g % 2) % R
        role[g * R + lead] = LEADER
        commit[g * R:(g + 1) * R] = 900 + g
        commit[g * R + node] -= 10
        pr[g * R + lead] = REPLICATE
    return {"role": role, "commit": commit, "pr_state": pr}


def still_more_than_an_append_behind(level):
    level["commit"][2 * R + 1] -= 55
    return 1


def still_probed_by_its_leader(level):
    lead = int(np.argmax(level["role"][3 * R:4 * R] == LEADER))
    level["pr_state"][3 * R + lead, 1] = 0
    return 1


def two_groups_without_a_leader(level):
    level["role"][:2 * R] = 0
    return 2


@pytest.mark.parametrize("fault", [
    None, still_more_than_an_append_behind, still_probed_by_its_leader,
    two_groups_without_a_leader],
    ids=lambda f: getattr(f, "__name__", "sound"))
def test_level_checks_count_the_replicas_not_level(fault):
    level = level_state()
    want = fault(level) if fault else 0
    checks = level_checks(level, 1, G, R, 64)
    assert checks[0].value == want and checks[1].ok
    assert verdict(checks) == (want == 0)
    # Another node's replicas are nobody's to check here: the row the
    # fault sits in is node 1's.
    if fault is still_probed_by_its_leader:
        assert verdict(level_checks(level, 0, G, R, 64))


def reference_of(st):
    def state(g):
        return [tuple(int(st[f][g * R + s]) for f in
                      ("term", "role", "lead", "commit", "last"))
                for s in range(R)]

    def log(g, s):
        i = g * R + s
        return [(j, 2 if j < 150 else 3) for j in range(
            int(st["snap_index"][i]) + 1, int(st["last"][i]) + 1)]

    return state, log


def test_engine_checks_hold_the_run_table_to_the_references_log():
    st = sound_state()
    classes = np.arange(G) % 2
    # Groups of a class are equal row for row: make them so.
    for f, arr in st.items():
        rows = arr.reshape((G, R) + arr.shape[1:])
        for g in range(G):
            rows[g] = rows[g % 2]
    state, log = reference_of(st)
    sound = engine_checks(st, G, R, classes, [0, 1], state, log)
    assert verdict(sound), [c for c in sound if not c.ok]
    # A representation that lost depth: the floor is where it was, the
    # run that covered the entries above it is gone.
    lost = {f: arr.copy() for f, arr in st.items()}
    lost["log_term"][0 * R + 2, :, 2] = 0
    bad = {c.name for c in engine_checks(lost, G, R, classes, [0, 1], state,
                                         log) if not c.ok}
    assert bad == {"sampled_replicas_log_differs_from_reference",
                   "groups_unequal_within_leader_class"}
    # A class member whose table differs in a slot nobody reads any
    # more is still not its class.
    stale = {f: arr.copy() for f, arr in st.items()}
    stale["log_term"][4 * R, :, 7] = (3, 1)
    bad = {c.name for c in engine_checks(stale, G, R, classes, [0, 1],
                                         state, log) if not c.ok}
    assert bad == {"groups_unequal_within_leader_class"}
    assert not verdict(engine_checks(st, G, R, classes, [], state, log))


# -- the readers, each on a made context ---------------------------------------------


def with_entries(dst) -> str:
    """A tiny root (its ``BENCHMARK.json`` holds the cell's own
    entries since PR 52) whose period is 512 rounds."""
    return cell_root(str(dst), CELL)


def reader_ctx():
    return {"raw": {
        "catchup": {
            "before": {"behind_rounds": 100, "catchup_appends": 10,
                       "catchup_entries": 640},
            "after": {"behind_rounds": 100 + 8 * 70, "catchup_appends": 538,
                      "catchup_entries": 640 + 528 * 62}},
        "telemetry": {"before": {"append_rejected": 4, "sent_snapshot": 0},
                      "after": {"append_rejected": 12, "sent_snapshot": 0}},
        "replicas_returned": 8, "log_depth_entries": 5120.0}}


def test_each_new_reader_on_a_made_context(tmp_path):
    ctx = reader_ctx()
    assert reader.per_return(ctx, "behind_rounds") == 70.0
    assert reader.telemetry_per_return(ctx, "append_rejected") == 1.0
    assert reader.telemetry_per_return(ctx, "sent_snapshot") == 0.0
    assert reader.ents_per_app(ctx) == 62.0
    assert reader.depth_entries(ctx) == 5120.0
    cell = harness.Cell(with_entries(tmp_path), CELL)
    got = harness.per_layer_metrics(cell, dict(
        ctx, trace=None, gaps=None, config=cell.config,
        traffic=cell.traffic, device={"kind": "cpu"},
        compile={"in_window": 0, "cache_misses": 0},
        memory_peak_bytes=2 << 30))
    assert {n: got[n]["value"] for n in NEW if n in got} == {
        "catchup.rounds_to_level": 70.0, "catchup.rejects_per_return": 1.0,
        "catchup.snapshots_per_return": 0.0, "catchup.ents_per_app": 62.0,
        "log.depth_entries": 5120.0}


def test_the_readers_find_nothing_in_another_drivers_run():
    """The parent, and every other cell: no count, no return, nothing
    raised."""
    other = {"raw": {"groups": 8, "rounds": 64, "telemetry": {
        "before": {"append_rejected": 0, "sent_snapshot": 0},
        "after": {"append_rejected": 5, "sent_snapshot": 7}}}}
    for ctx in (other, {"raw": {}}):
        assert reader.per_return(ctx, "behind_rounds") is None
        assert reader.telemetry_per_return(ctx, "sent_snapshot") is None
        assert reader.ents_per_app(ctx) is None
        assert reader.depth_entries(ctx) is None
    ctx = reader_ctx()
    ctx["raw"]["replicas_returned"] = 0  # a window without a heal
    assert reader.per_return(ctx, "behind_rounds") is None
    assert reader.telemetry_per_return(ctx, "append_rejected") is None
    assert reader.per_return(reader_ctx(), "no_such_count") is None


# -- the cell driven tiny ----------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return with_entries(tmp_path_factory.mktemp("catchup") / "root")


@pytest.fixture(scope="module")
def driven(root):
    """The cell's driver after a tiny window (one period: a node away
    for 192 rounds, 384 entries behind, six appends of 64 deep), kept
    open for the controls."""
    cell = harness.Cell(root, CELL)
    seed = 2**31 + 50
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_catchup.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    raw.update(driver.window_counters())
    yield driver, load, raw, cell
    driver.close()


def test_the_cell_is_correct_and_its_layers_are_on_the_line(root, capsys):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 51, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 19
    raw = ctx["raw"]
    assert raw["rounds"] >= TINY["period_rounds"]
    # The window opens 64 rounds in (the warm-up call); a node heals at
    # round 256 of every 512.
    heals = sum(1 for t in range(64, 64 + raw["rounds"]) if t % 512 == 256)
    assert raw["replicas_returned"] == 8 * heals > 0
    got = harness.per_layer_metrics(cell, ctx)
    assert got["catchup.snapshots_per_return"]["value"] == 0.0
    assert 1 <= got["catchup.rejects_per_return"]["value"] <= 3
    # 384 entries behind: six rounds deep at 64 an append, and the
    # probe's round trip before them.
    assert 4 <= got["catchup.rounds_to_level"]["value"] < 16
    assert got["catchup.ents_per_app"]["value"] > 48
    assert got["log.depth_entries"]["value"] > 1000
    assert "round.log_pct" not in got  # no trace, nothing to read
    # The cell's own five counters through the harness, from
    # ``BENCHMARK.json`` itself; the driver says them on no line of
    # its own any more (a run's lines: the harness's tags alone).
    assert set(NEW[1:]) <= set(got)
    tags = {ln.split("]")[0] for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:")}
    assert not [t for t in tags if t.endswith("_layers")]
    # The bulk half ran in the rounds a returned replica was carried: a
    # few of the window's, and the lane is split (E 64, head 3).
    occ = raw["occupancy"]
    assert (occ["app_head"], occ["slot_bytes"][6]) == (3, 4 * 61)
    assert 0 < got["round.bulk_pct"]["value"] < 25
    assert got["round.bulk_pct"]["value"] == (
        100.0 * (occ["after"]["bulk"] - occ["before"]["bulk"])
        / raw["rounds"])
    assert harness.end_to_end_metrics(cell, ctx)[
        "group_rounds_per_s"]["value"] > 0


def test_the_window_holds_a_heal_and_no_snapshot(driven):
    driver, load, raw, _cell = driven
    assert raw["rounds"] >= load["period_rounds"] == 512
    moved = {k: raw["telemetry"]["after"][k] - v
             for k, v in raw["telemetry"]["before"].items()}
    assert moved["elections_won"] > 0 and moved["append_rejected"] > 0
    assert moved["sent_snapshot"] == 0 == moved["to_snapshot"]
    assert raw["replicas_returned"] >= driver.groups
    assert driver.heals(0, 1024) == 2 and driver.heals(256, 1) == 1
    assert driver.heals(257, 255) == 0


def test_sound_reference_is_correct_and_the_level_was_read(driven):
    driver, load, raw, _cell = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert driver.rounds_done % load["period_rounds"] == 0
    assert driver.level is not None and driver.derailed == []
    assert driver.level["node"] in (0, 1, 2)
    assert set(driver.final["totals"]) >= {"sent_snapshot", "to_snapshot"}
    final, calls = driver.final, driver.calls
    assert verdict(driver.check(load, raw))
    assert driver.final is final and driver.calls == calls


@pytest.mark.parametrize("control", engine_catchup.CONTROLS)
def test_control_is_not_correct(driven, control):
    driver, load, raw, _cell = driven
    checks = driver.check(load, raw, control=control)
    assert not verdict(checks)
    bad = {c.name for c in checks if not c.ok}
    assert bad <= {"sampled_replicas_state_differs_from_reference",
                   "sampled_replicas_log_differs_from_reference"}
    differing = {c.name: c.value for c in checks}[
        "sampled_replicas_state_differs_from_reference"]
    if control == "reference_window_32":
        # The reference holds 16 entries: every sampled log differs.
        assert "sampled_replicas_log_differs_from_reference" in bad
    else:
        assert differing > len(driver.derailed) * driver.cfg.num_replicas


def test_the_existing_control_script_names_commit_without_quorum(driven):
    """``benchmark/control.py`` passes ``control=True``."""
    driver, load, raw, _cell = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")


def test_a_program_without_the_field_refuses_the_sizes_at_once(monkeypatch):
    """What the parent commit does with this cell: its ``BatchedConfig``
    takes no ``log_runs``, so the driver's set-up raises before any
    device work."""
    import etcd_tpu.batched as batched

    fields = [f for f in batched.BatchedConfig._fields if f != "log_runs"]

    def parents(**kw):
        unknown = set(kw) - set(fields)
        if unknown:
            raise TypeError(f"unexpected keyword argument {unknown}")
        return batched.BatchedConfig(**kw)

    monkeypatch.setattr(batched, "BatchedConfig", parents)
    cell = harness.Cell(REPO, CELL)
    driver = engine_catchup.Driver(cell.config, cell.traffic, 1, "")
    with pytest.raises(TypeError, match="log_runs"):
        driver.setup(gen.make(cell.traffic, cell.config["sizes"], 1), gen)
    assert driver.eng is None
