"""Shared by the benchmark's CPU tests: a temporary copy of the benchmark
with every size cut to a few groups, so that the harness's own functions
can be driven end to end without the chip."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
XPROF = os.path.join(REPO, "artifacts", "tpu_r05", "xprof")

# The cells live when PR 36 made the per-layer list open, in their
# order: history, for the tests that state what a PR found. What an
# entry lists today is held by rule (``shared``, ``test_lists.py``).
CELLS_AT_36 = ["engine64k-r3.append", "engine10k-r5.append",
               "engine100k-r3.elections", "engine1m-r3.joint-readindex",
               "engine512k-r3of4.replace-readindex"]

# The accepted entries of layers that more than one cell's program runs
# as PR 52 found and left them (PR 42's ``LISTED_ELSEWHERE``, and PR
# 52's three occupancy counters): history, for the tests that state
# what a cell was listed by then. What the rule of ``test_lists.py``
# holds today is ``shared(b)``, from the file itself.
SHARED_AT_52 = [
    "round.route_pct", "route.roofline_pct", "round.tick_pct",
    "round.telemetry_pct", "round.control_pct", "round.propose_pct",
    "round.emit_pct", "round.unscoped_pct", "round.lanes_run",
    "scan.tiles_pct", "scan.watch_pct", "scan.carry_pct",
    "setup.jax_trace_s", "setup.jax_compile_s", "setup.pretrace_s",
    "setup.unspanned_s", "read.confirmed_per_kgr", "read.rounds_to_confirm",
    "round.rare_pct", "emit.ring_pct", "round.bulk_pct"]


def shared(b: dict) -> list:
    """The entries the rule of ``test_lists.py`` is about, from the
    file alone: an entry with a list that reads the device trace or the
    program's spans (a scope or a span a cell's program has decides,
    whoever brought the entry), or that lists more than one cell. Each
    lists a cell if and only if that cell's run gives its reader
    something to read. What is left, a counter's view that one cell
    alone lists (``election.*``, ``reconf.*``, ...), is that cell's
    choice of what to say of its traffic: another cell's telemetry
    would give the reader a number too, and nobody asked for it."""
    return [m["name"] for m in b["per_layer"] if "workloads" in m and (
        m["source"] in ("device_trace", "program_span")
        or len(m["workloads"]) > 1)]


TINY_TRAFFIC = {
    "put": {"clients": 16, "ramp_s": 0.3},
    "lread": {"clients": 4, "preload_keys_per_group": 2, "ramp_s": 0.3},
    "append": {"rounds_per_call": 4},
}


def _edit(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def copy_root(dst: str) -> str:
    """``BENCHMARK.json`` and ``benchmark/`` as committed, in ``dst``."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    return dst


def cut_root(dst: str, groups: int = 8) -> str:
    """Every configuration under ``dst`` cut to a few groups, the
    served traffic and ``append`` to a few clients and rounds, the
    parked cells' entries added."""
    for name in os.listdir(os.path.join(dst, "benchmark", "configs")):
        def cut(c):
            c["sizes"]["num_groups"] = groups
            c["shadow_groups"] = 4
        _edit(os.path.join(dst, "benchmark", "configs", name), cut)
    for name, upd in TINY_TRAFFIC.items():
        _edit(os.path.join(dst, "benchmark", "traffic", name + ".json"),
              lambda c, upd=upd: c.update(upd))
    _edit(os.path.join(dst, "BENCHMARK.json"), add_parked)
    return dst


def tiny_root(dst: str, groups: int = 8) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``dst`` and cut
    the sizes; returns ``dst``."""
    return cut_root(copy_root(dst), groups)


def parked() -> dict:
    with open(os.path.join(REPO, "benchmark", "parked", "served.json")) as f:
        return json.load(f)


def add_parked(b: dict) -> None:
    """The served cells are parked (PERF.md, Open questions): every file
    they name is kept, their entries are in ``benchmark/parked/``. The
    tests add the entries, as the PR that takes a cell up again will."""
    p = parked()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        b[key].extend(p[key])


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def edited_copy(tmp_path, edit) -> dict:
    """``BENCHMARK.json`` as a temporary copy of it reads after
    ``edit``: what a rule on the file is shown to admit and to refuse."""
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    _edit(path, edit)
    with open(path) as f:
        return json.load(f)


def swap(rows: list, i: int, j: int) -> None:
    rows[i], rows[j] = rows[j], rows[i]


def listed_cells(names) -> dict:
    """{name: its ``workloads``} of the live per-layer entries of these
    names, which have to stand in ``per_layer`` in this order, one
    after the other."""
    rows = bench()["per_layer"]
    at = [m["name"] for m in rows].index(names[0])
    assert [m["name"] for m in rows[at:at + len(names)]] == list(names)
    return {m["name"]: m["workloads"] for m in rows[at:at + len(names)]}


def in_workloads_order(b: dict, m: dict) -> bool:
    """An entry's cells are cells of the file, each once, in the order
    ``workloads`` has them: a later PR's cell goes in where that order
    puts it, which is the end while cells are only appended."""
    order = [w["name"] for w in b["workloads"]]
    at = [order.index(c) for c in m["workloads"]]
    return at == sorted(set(at))


LATER_CONFIG = "later-r3-deeplog"
LATER = LATER_CONFIG + ".later-catchup"
LATER_ENTRY = "later.dispatch_ms"


def one_more(b: dict) -> None:
    """What the next PRs do to ``BENCHMARK.json``: a `model_config`
    PR's configuration, cell and name under its end-to-end metric, each
    at the end of its list, the cell's name at the end of every list
    that its run gives something to read (here: the lists of the
    newest cell, whose deployment it copies), and a `tracing` PR's
    entry at the end of ``per_layer`` (without ``workloads``: every
    cell reports it)."""
    like = b["workloads"][-1]
    cfg = [c for c in b["configs"] if c["name"] == like["config"]][0]
    b["configs"].append(dict(
        cfg, name=LATER_CONFIG,
        file=f"benchmark/configs/{LATER_CONFIG}.json"))
    b["workloads"].append(dict(
        like, name=LATER, config=LATER_CONFIG,
        traffic=LATER.split(".", 1)[1]))
    for m in b["end_to_end"] + b["per_layer"]:
        if like["name"] in m.get("workloads", []):
            m["workloads"].append(LATER)
    b["per_layer"].append({
        "name": LATER_ENTRY, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "closed-loop engine",
        "moves": "group_rounds_per_s"})


def one_more_root(dst: str) -> str:
    """``one_more`` with its files, as the PRs that make those edits
    would bring them and with no edit to a file that is there: the
    later cell's configuration and traffic (the newest cell's, under
    names of their own), its tiny cuts (``<dst>/tiny/``: the newest
    cell's own, without which it does not run in seconds), the later
    entry's ``layer_metrics`` file (a reader the benchmark has); then
    cut as ``tiny_root`` cuts. The later cell runs on it through the
    per-cell tests of ``test_lists.py`` as a committed cell does."""
    copy_root(dst)
    b = bench()
    like = b["workloads"][-1]
    cfg = [c for c in b["configs"] if c["name"] == like["config"]][0]
    base = os.path.join(dst, "benchmark")
    shutil.copy(os.path.join(dst, cfg["file"]),
                os.path.join(base, "configs", LATER_CONFIG + ".json"))
    _edit(os.path.join(base, "configs", LATER_CONFIG + ".json"),
          lambda c: c.update(name=LATER_CONFIG))
    traffic = LATER.split(".", 1)[1]
    shutil.copy(os.path.join(base, "traffic", like["traffic"] + ".json"),
                os.path.join(base, "traffic", traffic + ".json"))
    _edit(os.path.join(base, "traffic", traffic + ".json"),
          lambda t: t.update(name=traffic))
    with open(os.path.join(base, "layer_metrics",
                           "engine.dispatch_ms.json")) as f:
        spec = json.load(f)
    with open(os.path.join(base, "layer_metrics", LATER_ENTRY + ".json"),
              "w") as f:
        json.dump(dict(spec, name=LATER_ENTRY), f, indent=1)
    os.makedirs(os.path.join(dst, "tiny"))
    mine = os.path.join(TINY_DIR, like["name"] + ".json")
    if os.path.exists(mine):
        shutil.copy(mine, os.path.join(dst, "tiny", LATER + ".json"))
    _edit(os.path.join(dst, "BENCHMARK.json"), one_more)
    return dst


def cell_as_committed(cell_name: str, root: str = REPO):
    """(the cell's entry, its configuration's sizes) from the files
    under ``root``, nothing cut."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    w = [w for w in b["workloads"] if w["name"] == cell_name][0]
    cfg = [c for c in b["configs"] if c["name"] == w["config"]][0]
    with open(os.path.join(root, cfg["file"])) as f:
        return w, json.load(f)["sizes"]


def real_tiles(cell_name: str, root: str = REPO) -> int:
    """The tiles the cell's closed loop runs in at its real size (a
    node's, placed over nodes): ``engine.scan_tiles`` of the
    configuration as committed, from the shape alone."""
    from etcd_tpu.batched import BatchedConfig
    from etcd_tpu.batched.engine import scan_tiles

    w, sizes = cell_as_committed(cell_name, root)
    return scan_tiles(
        BatchedConfig(**{k: v for k, v in sizes.items()
                         if k in BatchedConfig._fields}),
        nodes=w["chips"] == 4)


# -- a cell's tiny run, and what its program holds -------------------------------

TINY_DIR = os.path.join(REPO, "tests", "benchmark", "tiny")


def _update(obj: dict, cuts: dict) -> None:
    """``dict.update`` that goes into ``sizes``-like groups: a nested
    dict of ``cuts`` updates the group of the same key."""
    for k, v in cuts.items():
        if isinstance(v, dict) and isinstance(obj.get(k), dict):
            _update(obj[k], v)
        else:
            obj[k] = v


def apply_tiny(root: str, cell_name: str, tiny_dir: str = TINY_DIR) -> None:
    """The cuts a cell needs beyond ``tiny_root``'s to run in seconds
    on the CPU, from its own file ``tiny/<cell>.json`` (``config`` and
    ``traffic``: keys to update in the cell's two files under ``root``);
    a cell without such a file needs none. Data beside the cell: a PR
    that brings a cell brings the file, and edits nothing here."""
    path = os.path.join(tiny_dir, cell_name + ".json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        cuts = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    w = [w for w in b["workloads"] if w["name"] == cell_name][0]
    cfg = [c for c in b["configs"] if c["name"] == w["config"]][0]
    _edit(os.path.join(root, cfg["file"]),
          lambda c: _update(c, cuts.get("config", {})))
    _edit(os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json"),
          lambda t: _update(t, cuts.get("traffic", {})))


def cell_root(dst: str, cell_name: str) -> str:
    """``tiny_root`` with the one cell's own cuts: a root for that cell
    alone (two cells may cut one traffic file differently)."""
    apply_tiny(tiny_root(dst), cell_name)
    return dst


def later_root(dst: str) -> str:
    """``one_more_root`` cut to size, the later cell's own cuts from
    the file it brought."""
    apply_tiny(cut_root(one_more_root(dst)), LATER,
               os.path.join(dst, "tiny"))
    return dst


def fresh_recorder() -> None:
    """The program's span recorder emptied: the readers of spans take
    every span the process holds, and a test process runs cell after
    cell where the benchmark runs one."""
    from etcd_tpu.obs import spans

    spans.DEFAULT.__init__(spans.DEFAULT.slots, spans.DEFAULT._registry)


def _bodies(eqn):
    from jax.extend import core as jex_core

    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def jaxpr_scopes(jaxpr, registered, outer: str = "") -> set:
    """The registered ``named_scope``s that equations of a jaxpr stand
    under, walked through every body as lowering composes an op's
    ``tf_op`` (``tests/batched/test_scopes.scoped``, copied: the
    innermost registered name wins; an equation that encloses others
    is read through them)."""
    from benchmark.reduce.trace import SCOPE_RE

    found = set()
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        bodies = list(_bodies(eqn))
        for body in bodies:
            found |= jaxpr_scopes(body, registered, stack)
        if bodies:
            continue
        hits = [h for h in SCOPE_RE.findall(stack) if h in registered]
        if hits:
            found.add(hits[-1])
    return found


class LoopScopes:
    """While it is open, every ``MultiRaftEngine`` built hands its
    closed loop's calls through here: the first call of each form (the
    static ``rounds`` and which schedules it is handed) is traced to a
    jaxpr once more (no compile; JAX keeps the trace for the call
    itself) and the registered scopes (``step.DEVICE_SCOPES``) of its
    equations kept. ``last``: those of the form called last, which is
    the window's program and so the traced calls': what a profiler's
    trace of the cell on the chip holds, read from the program itself,
    from no table of switches or names."""

    def __init__(self) -> None:
        self.by_form: dict = {}
        self.last = None

    def __enter__(self):
        from etcd_tpu.batched import MultiRaftEngine, step

        registered = {scope for _l, _n, scope in step.DEVICE_SCOPES}
        self._real = real = MultiRaftEngine._init
        rec = self

        def _init(eng, *a, **k):
            real(eng, *a, **k)
            jitted = eng._closed_loop

            def closed_loop(*args, **kwargs):
                form = (id(eng), args[8], tuple(x is None for x in args),
                        len(args), tuple(sorted(kwargs)))
                if form not in rec.by_form:
                    rec.by_form[form] = jaxpr_scopes(
                        jitted.trace(*args, **kwargs).jaxpr, registered)
                rec.last = rec.by_form[form]
                return jitted(*args, **kwargs)

            eng._closed_loop = closed_loop

        MultiRaftEngine._init = _init
        return self

    def __exit__(self, *exc):
        from etcd_tpu.batched import MultiRaftEngine

        MultiRaftEngine._init = self._real
        return False


def run_tiny(root: str, cell_name: str, seed: int = 2**31 + 52,
             tiles: int = 1):
    """(the cell, its run's ``ctx``, its checks, the scopes its
    window's program holds) of one tiny run on the CPU through the
    harness; ``tiles`` > 1 runs the closed loop in two tiles, as a cell
    tiled at its real size runs on the chip."""
    import time

    from benchmark import harness
    from etcd_tpu.batched import engine as engine_mod

    fresh_recorder()
    cell = harness.Cell(root, cell_name)
    rows = int(cell.config["sizes"]["num_groups"]) * (
        1 if cell.chips == 4 else int(cell.config["sizes"]["num_replicas"]))
    save = engine_mod.TILE_ROWS, engine_mod.TILE_ALIGN
    if tiles > 1:
        engine_mod.TILE_ROWS, engine_mod.TILE_ALIGN = rows // 2, 1
    try:
        with LoopScopes() as scopes:
            ctx, checks = harness.measure(
                cell, seed, 0.3, False, time.perf_counter(),
                require_tpu=False)
    finally:
        engine_mod.TILE_ROWS, engine_mod.TILE_ALIGN = save
    return cell, ctx, checks, scopes.last or set()


# What ``per_layer`` holds without a list: every cell reports these.
# At least these, by name; a later PR may append one more.
UNLISTED = {
    "round.device_ms", "round.deliver_pct", "engine.call_gap_ms",
    "device.hbm_peak_gb", "compile.in_window", "compile.cache_misses",
    "engine.dispatch_ms", "engine.late_ms", "setup.engine_init_s",
    "setup.elect_s", "setup.first_scan_s"}
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}


def own_entries(b: dict, names, at: int, cell: str, new_layers=()) -> list:
    """A PR's own entries, held by index from the front: they stand at
    ``at``, after what was there when the PR came, in its order and
    nowhere else; each has the seven keys, a layer the file already
    named, its file under ``layer_metrics/`` with the same name, unit,
    layer and end-to-end metric, and lists ``cell`` first (a later
    cell whose run gives the reader something goes after it; a layer
    the PR itself named first: ``new_layers``). What follows them is a
    later PR's: the rule says nothing of it."""
    rows = b["per_layer"]
    mine = rows[at:at + len(names)]
    assert [m["name"] for m in mine] == list(names)
    rest = rows[:at] + rows[at + len(names):]
    assert not set(names) & {m["name"] for m in rest}
    layers = {m["layer"] for m in rows[:at]} | set(new_layers)
    for m in mine:
        assert set(m) == ENTRY_KEYS and m["workloads"][0] == cell
        assert in_workloads_order(b, m)
        assert m["layer"] in layers
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (m["name"], m["unit"], m["layer"], m["moves"])
        assert "workloads" not in spec
    return mine


def reaches(b: dict, cell: str) -> set:
    """The names of the per-layer entries that reach ``cell``: those
    without a list and those that list it."""
    return {m["name"] for m in b["per_layer"]
            if "workloads" not in m or cell in m["workloads"]}


def shared_with(b: dict, cell: str) -> set:
    """The shared entries (``shared(b)``) that list ``cell``."""
    names = set(shared(b))
    return {m["name"] for m in b["per_layer"]
            if m["name"] in names and cell in m["workloads"]}
