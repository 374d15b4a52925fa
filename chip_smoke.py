"""chip_smoke.py — the quickest proof that etcd_tpu still starts on the chip.

    python3 chip_smoke.py [--seed N]

One process; it imports JAX itself, sets no platform and starts no
child. Without a TPU it exits non-zero within seconds and prints no
result. Phases, each printed with its wall and compile seconds; an
exception in any of them ends the run non-zero:

* device    — ``jax.devices()`` must be TPUs; versions and the active
              compile-cache directory are printed.
* engine    — the closed-loop engine at full width
              (``benchlib.make_bench_engine``, G=65536, R=3): every
              group elected, then 16-round scans under steady proposals
              with the transfer guard on ``disallow``. Checked outside
              the timed span: the first groups' per-replica state and
              log equal ``shadow.ShadowCluster`` stepped through the
              same schedule, and (all groups run one schedule) every
              other group's rows equal group 0's.
* served    — the in-process served path (``MultiRaftCluster``: three
              ``MultiRaftMember`` over ``InProcRouter``, G=1024, R=3,
              the member's default config; BASELINE.json config 2).
              16 keys x G groups of 8 B keys / 256 B values made from
              the seed are put on the leaders in waves; then a sample
              is read with ``linearizable_get`` (device ReadIndex),
              every acknowledged put is read back from all three
              members, ``multiraft_hash_check`` and
              ``committed_never_lost`` run over all G, every WAL shows
              fsyncs, and after ``stop()`` and a re-open on the same
              directory the sample is read back again.
* four_chips — with >= 4 devices, the served phase again with each
              member's rows sharded over a 4-device mesh; every device
              must hold a quarter of the rows. Skipped on one chip.

Sizes are the constants below. Times printed here are observations of
one run, not benchmark metrics. The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; the per-phase
report also lands in ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import statistics
import tempfile
import time
from collections import deque

ENGINE_GROUPS = 65536
ENGINE_ROUNDS_PER_CALL = 16
ENGINE_CALLS = 4
ENGINE_SHADOW_GROUPS = 32
SERVED_GROUPS = 1024
SERVED_KEYS_PER_GROUP = 16
KEY_BYTES, VALUE_BYTES = 8, 256
SERVED_SAMPLE = 64
MEMBERS = 3
MESH_DEVICES = 4
# The contract allows 1200 s, compilation included; past this the
# watchdog dumps every thread's stack and exits non-zero.
WATCHDOG_S = 1150

_HERE = os.path.dirname(os.path.abspath(__file__))

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # Includes the persistent-cache fetch when the program was a hit.
    "/jax/core/compile/backend_compile_duration",
)


class CompileMeter:
    """Sums JAX's own compile events (trace + lower + backend compile or
    cache fetch), keeps each program's backend seconds by name, and
    counts persistent-cache hits and misses."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.programs: list = []  # [name, backend seconds], in order
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
            if event == _COMPILE_EVENTS[-1]:
                self.programs.append([kw.get("fun_name", "?"), secs])

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, len(self.programs), self.hits, self.misses


def _say(obj: dict) -> None:
    print("[chip_smoke] " + json.dumps(obj), flush=True)


def _cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(f.endswith("-cache") for f in os.listdir(cache_dir))


# -----------------------------------------------------------------------------
# device
# -----------------------------------------------------------------------------


def phase_device() -> dict:
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found platform={d.platform!r} "
            f"device_kind={d.device_kind!r} count={len(devs)}")

    from etcd_tpu.batched.compile_cache import enable_compile_cache

    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devs),
        "jax": md.version("jax"),
        "jaxlib": md.version("jaxlib"),
        "libtpu": md.version("libtpu"),
        "compile_cache_dir": enable_compile_cache(),
    }


# -----------------------------------------------------------------------------
# engine
# -----------------------------------------------------------------------------


def phase_engine(groups: int = ENGINE_GROUPS,
                 rounds_per_call: int = ENGINE_ROUNDS_PER_CALL,
                 calls: int = ENGINE_CALLS,
                 shadow_groups: int = ENGINE_SHADOW_GROUPS) -> dict:
    import jax
    import numpy as np

    from etcd_tpu.batched.shadow import ShadowCluster
    from etcd_tpu.batched.state import BatchedState
    from etcd_tpu.tools.benchlib import make_bench_engine

    t0 = time.perf_counter()
    # Campaign round + 4 settle rounds; asserts every group elected.
    eng, props = make_bench_engine(groups)
    jax.block_until_ready(eng.state.commit)
    elect_s = time.perf_counter() - t0
    cfg = eng.cfg
    r = cfg.num_replicas

    # The first call of the 16-round scan compiles (and runs outside the
    # transfer guard, which fences warm dispatch only); the timed calls
    # after it are all guarded.
    t0 = time.perf_counter()
    eng.run_rounds(rounds_per_call, tick=True, propose_n=props)
    jax.block_until_ready(eng.state.commit)
    first_call_s = time.perf_counter() - t0
    call_s = []
    for _ in range(calls):
        t0 = time.perf_counter()
        eng.run_rounds(rounds_per_call, tick=True, propose_n=props)
        # jitlint: waive(sync-in-loop) -- the fence IS the per-call timing: one sync per 16-round scan, four in all
        jax.block_until_ready(eng.state.commit)
        call_s.append(time.perf_counter() - t0)

    # -- answers, outside any timed span --------------------------------------
    st = {f: np.asarray(getattr(eng.state, f)) for f in BatchedState._fields}
    assert (st["commit"].reshape(groups, r)[:, 0] > 0).all(), \
        "a group committed nothing under steady proposals"

    # Every group ran the identical schedule, so every group's rows must
    # equal group 0's. randomized_timeout is the one lane seeded by the
    # instance id (step._rand_timeout) and differs by construction.
    same = [f for f in BatchedState._fields if f != "randomized_timeout"]
    for f in same:
        rows = st[f].reshape((groups, r) + st[f].shape[1:])
        bad = np.nonzero((rows != rows[0]).reshape(groups, -1).any(axis=1))[0]
        assert not len(bad), (
            f"field {f}: {len(bad)} groups differ from group 0 "
            f"(first {bad[:8].tolist()})")

    # The plain host oracle through the same schedule.
    n_sh = min(shadow_groups, groups)
    shadows = [
        ShadowCluster(
            r, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, group=g,
            deterministic_timeouts=True,
            auto_compact_window=cfg.window,
            max_ents=cfg.max_ents_per_msg,
            deliver_shape=cfg.deliver_shape)
        for g in range(n_sh)
    ]
    for sh in shadows:
        sh.round(campaigns=[0])
        for _ in range(4):
            sh.round()
        for _ in range(rounds_per_call * (calls + 1)):
            sh.round(tick=True, proposals={0: cfg.max_props_per_round})
    got = [
        tuple(int(st[f][i]) for f in ("term", "role", "lead", "commit",
                                      "last"))
        for i in range(n_sh * r)
    ]
    want = [s for sh in shadows for s in sh.snapshot_state()]
    assert got == want, f"device state != shadow oracle: {got} != {want}"
    for i in range(n_sh * r):
        lo, hi = int(st["snap_index"][i]), int(st["last"][i])
        ring = st["log_term"][i]
        dev_log = [(j, int(ring[j % cfg.window]))
                   for j in range(lo + 1, hi + 1)]
        assert dev_log == shadows[i // r].log_terms(i % r), (
            f"instance {i}: device log != shadow oracle log")

    ms_per_round = [s / rounds_per_call * 1e3 for s in call_s]
    return {
        "groups": groups, "replicas": r, "window": cfg.window,
        "layout": "minor" if cfg.lanes_minor else "major",
        "deliver": cfg.deliver_shape,
        "transfer_guard": os.environ.get("ETCD_TPU_TRANSFER_GUARD", ""),
        "rounds": rounds_per_call * (calls + 1) + 5,
        "elect_s": elect_s,
        "first_call_s": first_call_s,
        "ms_per_round_median": statistics.median(ms_per_round),
        "ms_per_round_calls": ms_per_round,
        "commit_min": int(st["commit"].min()),
        "shadow_groups_equal": n_sh,
        "groups_equal_group0": groups,
        "fields_compared": len(same),
    }


# -----------------------------------------------------------------------------
# served path
# -----------------------------------------------------------------------------


def _make_load(groups: int, keys_per_group: int, seed: int):
    """{(group, key): value}: 8 B keys / 256 B values in bulk from the
    seed (the upstream benchmark's put shape). Key bytes are drawn from
    1..255: GroupKV frames a put as ``P key NUL value``, so a key cannot
    hold a NUL."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = groups * keys_per_group
    kb = rng.integers(1, 256, size=n * KEY_BYTES, dtype=np.uint8).tobytes()
    vb = rng.bytes(n * VALUE_BYTES)
    load = {}
    for i in range(n):
        g = i // keys_per_group
        load[(g, kb[i * KEY_BYTES:(i + 1) * KEY_BYTES])] = (
            vb[i * VALUE_BYTES:(i + 1) * VALUE_BYTES])
    assert len(load) == n, "seeded keys collided within a group"
    return load


def _leader(cluster, group: int):
    for m in cluster.members.values():
        if m.is_leader(group):
            return m
    return None


def _put_waves(cluster, load: dict, wave_keys: int,
               timeout: float = 300.0) -> dict:
    """Propose every put on its group's leader, `wave_keys` keys per
    group in flight at a time; a put is acknowledged once its proposer
    has applied it (applied at the leader, hence committed). A proposal
    a deposed leader swallowed is re-proposed — puts are idempotent.
    Returns the acknowledged {(group, key): value}."""
    from etcd_tpu.batched.hosting import GroupKV

    by_group: dict = {}
    for (g, k), v in load.items():
        by_group.setdefault(g, deque()).append((k, v))
    acked: dict = {}
    deadline = time.monotonic() + timeout
    while by_group:
        todo = deque()
        for g in list(by_group):
            q = by_group[g]
            for _ in range(min(wave_keys, len(q))):
                todo.append((g,) + q.popleft())
            if not q:
                del by_group[g]
        inflight: deque = deque()
        while todo or inflight:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"put load: {len(acked)}/{len(load)} acknowledged "
                    f"after {timeout}s")
            for _ in range(len(todo)):
                g, k, v = todo.popleft()
                m = _leader(cluster, g)
                if m is not None and m.propose(
                        g, GroupKV.put_payload(k, v)):
                    inflight.append((g, k, v, m, time.monotonic() + 5.0))
                else:
                    todo.append((g, k, v))
            for _ in range(len(inflight)):
                g, k, v, m, retry_at = inflight.popleft()
                if m.get(g, k) == v:
                    acked[(g, k)] = v
                elif time.monotonic() > retry_at:
                    todo.append((g, k, v))
                else:
                    inflight.append((g, k, v, m, retry_at))
            time.sleep(0.005)
    return acked


def _read_sample(cluster, sample, timeout: float = 120.0) -> int:
    """linearizable_get of each sampled key on its group's leader (the
    device ReadIndex round), 16 clients at a time — one device round
    serves every group's open batch. A leader change mid-read is
    retried like a client following leader hints."""
    from concurrent.futures import ThreadPoolExecutor

    from etcd_tpu.batched.hosting import NotLeaderError

    deadline = time.monotonic() + timeout

    def read(item) -> None:
        (g, k), v = item
        while True:
            m = _leader(cluster, g)
            try:
                if m is not None:
                    got = m.linearizable_get(g, k, timeout=10.0)
                    assert got == v, (
                        f"linearizable_get g{g} {k!r}: wrong value")
                    return
            except (NotLeaderError, TimeoutError):
                pass
            if time.monotonic() > deadline:
                raise TimeoutError(f"linearizable_get g{g} {k!r}")
            time.sleep(0.02)

    with ThreadPoolExecutor(max_workers=16) as pool:
        for fut in [pool.submit(read, item) for item in sample]:
            fut.result()
    return len(sample)


def phase_served(groups: int = SERVED_GROUPS,
                 keys_per_group: int = SERVED_KEYS_PER_GROUP,
                 seed: int = 0, sample_n: int = SERVED_SAMPLE,
                 mesh_devices: int = 0) -> dict:
    import numpy as np

    from etcd_tpu.batched.hosting import MultiRaftCluster
    from etcd_tpu.functional.checker import (
        committed_never_lost,
        multiraft_hash_check,
    )

    load = _make_load(groups, keys_per_group, seed)
    items = list(load.items())
    pick = np.random.default_rng(seed + 1).choice(
        len(items), size=min(sample_n, len(items)), replace=False)
    sample = [items[i] for i in pick.tolist()]
    out: dict = {"groups": groups, "members": MEMBERS,
                 "mesh_devices": mesh_devices}

    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        t0 = time.perf_counter()
        c = MultiRaftCluster(data_dir, num_members=MEMBERS,
                             num_groups=groups, mesh_devices=mesh_devices)
        try:
            if mesh_devices:
                for m in c.members.values():
                    shards = m.rn.state.term.addressable_shards
                    rows = {str(s.device): s.data.shape[0] for s in shards}
                    assert len(rows) == mesh_devices and all(
                        n == groups // mesh_devices
                        for n in rows.values()), (
                        f"member {m.id}: rows per device {rows}, want "
                        f"{groups // mesh_devices} on each of "
                        f"{mesh_devices}")
                    out["rows_per_device"] = rows
            leads = c.wait_leaders(timeout=300.0)
            assert (leads > 0).all()
            out["elect_s"] = time.perf_counter() - t0
            cfg = next(iter(c.members.values())).rn.cfg
            out["config"] = (
                f"W={cfg.window} E={cfg.max_ents_per_msg} "
                f"P={cfg.max_props_per_round} deliver={cfg.deliver_shape}")

            t0 = time.perf_counter()
            acked = _put_waves(c, load, cfg.max_props_per_round)
            load_s = time.perf_counter() - t0
            assert acked == load, (
                f"{len(acked)}/{len(load)} puts acknowledged")
            out.update(puts=len(acked), load_s=load_s,
                       puts_per_s=len(acked) / load_s)

            t0 = time.perf_counter()
            out["linearizable_reads"] = _read_sample(c, sample)
            out["linearizable_s"] = time.perf_counter() - t0

            # Every acknowledged put read back from every member's
            # applied state against the host dict of what was acked.
            members = list(c.members.values())
            committed_never_lost(members, acked, timeout=120.0)
            out["reads_back"] = len(acked) * len(members)
            out["kv_hash_groups"] = len(
                multiraft_hash_check(members, timeout=120.0))
            syncs = {m.id: m.wal.sync_stats()[0] for m in members}
            assert all(n > 0 for n in syncs.values()), (
                f"a member's WAL shows no fsync: {syncs}")
            out["wal_fsyncs"] = syncs
            out["rounds"] = {m.id: m.stats["rounds"] for m in members}
        finally:
            c.stop()

        # An acknowledged write survives a restart: re-open on the same
        # directory (_replay) and read the sample back on every member.
        t0 = time.perf_counter()
        c2 = MultiRaftCluster(data_dir, num_members=MEMBERS,
                              num_groups=groups, mesh_devices=mesh_devices)
        try:
            deadline = time.monotonic() + 300.0
            while True:
                stale = [(m.id, g) for m in c2.members.values()
                         for (g, k), v in sample if m.get(g, k) != v]
                if not stale:
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"after restart, (member, group) {stale[:8]} do "
                        "not serve their acknowledged sample")
                time.sleep(0.1)
            out["restart_reads_back"] = len(sample) * MEMBERS
            out["restart_s"] = time.perf_counter() - t0
        finally:
            c2.stop()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    return out


def phase_four_chips(seed: int = 0) -> dict:
    import jax

    n = len(jax.devices())
    if n < MESH_DEVICES:
        return {"skipped": f"{n} device" + ("s" if n != 1 else "")}
    return phase_served(seed=seed, mesh_devices=MESH_DEVICES)


# -----------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    seed = ap.parse_args().seed

    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    # Every warm round dispatch below runs under
    # jax.transfer_guard("disallow") (analysis/sentinels.warm_guard).
    os.environ["ETCD_TPU_TRANSFER_GUARD"] = "disallow"

    meter = CompileMeter()
    report: dict = {"seed": seed, "phases": {}}

    def run(name: str, fn, **kwargs) -> dict:
        c0, p0, h0, m0 = meter.snapshot()
        t0 = time.perf_counter()
        res = fn(**kwargs)
        c1, p1, h1, m1 = meter.snapshot()
        res.update(
            wall_s=time.perf_counter() - t0, compile_s=c1 - c0,
            programs=p1 - p0, cache_hits=h1 - h0, cache_misses=m1 - m0,
            # In dispatch order: same-named scans differ by round count.
            programs_over_1s=[p for p in meter.programs[p0:p1]
                              if p[1] >= 1.0])
        report["phases"][name] = res
        _say({"phase": name, **res})
        return res

    dev = run("device", phase_device)
    # The oracle's RawNodes narrate every election at INFO on stderr.
    from etcd_tpu.raft.logger import DefaultLogger, set_logger

    set_logger(DefaultLogger(level=2))
    entries0 = _cache_entries(dev["compile_cache_dir"])
    run("engine", phase_engine)
    run("served", phase_served, seed=seed)
    run("four_chips", phase_four_chips, seed=seed)
    entries1 = _cache_entries(dev["compile_cache_dir"])
    report["compile_cache"] = {
        "dir": dev["compile_cache_dir"], "entries_before": entries0,
        "entries_after": entries1, "entries_added": entries1 - entries0}
    _say({"compile_cache": report["compile_cache"]})

    out_dir = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)


if __name__ == "__main__":
    main()
