"""Client sub-features: ordering guard, leasing cache, naming registry,
snapshot save (ref: client/v3/{ordering,leasing,naming,snapshot} tests)."""

import os
import threading
import time

import pytest

from etcd_tpu.client.client import Client
from etcd_tpu.client.leasing import LeasingKV
from etcd_tpu.client.naming import Endpoints
from etcd_tpu.client.ordering import OrderingKV, OrderViolationError
from etcd_tpu.client.snapshot import save as snapshot_save
from etcd_tpu.client.util import key_exists, key_missing
from etcd_tpu.raftexample.transport import InProcNetwork
from etcd_tpu.server import EtcdServer, ServerConfig
from etcd_tpu.server import api as sapi
from etcd_tpu.v3rpc.service import V3RPCServer

from ..server.test_etcdserver import wait_until


@pytest.fixture(scope="module")
def member(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("feat")
    net = InProcNetwork()
    srv = EtcdServer(
        ServerConfig(
            member_id=1, peers=[1], data_dir=str(tmp),
            network=net, tick_interval=0.01,
        )
    )
    rpc = V3RPCServer(srv, bind=("127.0.0.1", 0))
    wait_until(lambda: srv.is_leader(), msg="leader")
    yield srv, rpc
    rpc.stop()
    srv.stop()


class TestOrdering:
    def test_monotonic_reads_pass(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        kv = OrderingKV(c)
        kv.put(b"ok1", b"a")
        kv.get(b"ok1")
        kv.put(b"ok1", b"b")
        assert kv.get(b"ok1").kvs[0].value == b"b"
        c.close()

    def test_violation_detected(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        kv = OrderingKV(c)
        kv.put(b"ov", b"x")
        kv._prev_rev = 10**9  # simulate having seen a future revision
        with pytest.raises(OrderViolationError):
            kv.get(b"ov")
        c.close()

    def test_violation_fn_called(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        called = []
        kv = OrderingKV(c, violation_fn=called.append)
        kv.put(b"ov2", b"x")
        kv._prev_rev = 10**9
        with pytest.raises(OrderViolationError):
            kv.get(b"ov2")
        assert len(called) == 1
        c.close()


class TestUtil:
    def test_key_exists_missing_txn(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        c.put(b"exists", b"1")
        r = c.txn(sapi.TxnRequest(
            compare=[key_exists(b"exists")],
            success=[sapi.RequestOp(
                request_put=sapi.PutRequest(key=b"guarded", value=b"y")
            )],
        ))
        assert r.succeeded
        r = c.txn(sapi.TxnRequest(compare=[key_missing(b"exists")]))
        assert not r.succeeded
        c.close()


class TestLeasing:
    def test_cached_get_no_roundtrip(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        c.put(b"lk", b"v0")
        lkv = LeasingKV(c, "_leases/")
        try:
            r1 = lkv.get(b"lk")
            assert r1.kvs[0].value == b"v0"
            hits0 = lkv.cache_hits
            r2 = lkv.get(b"lk")
            assert r2.kvs[0].value == b"v0"
            assert lkv.cache_hits == hits0 + 1
        finally:
            lkv.close()
            c.close()

    def test_owner_write_through_updates_cache(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        lkv = LeasingKV(c, "_leases/")
        try:
            c.put(b"wt", b"orig")
            lkv.get(b"wt")  # acquire
            lkv.put(b"wt", b"updated")
            r = lkv.get(b"wt")  # cache hit
            assert r.kvs[0].value == b"updated"
            # Server agrees.
            assert c.get(b"wt").kvs[0].value == b"updated"
        finally:
            lkv.close()
            c.close()

    def test_nonowner_write_revokes_owner(self, member):
        _, rpc = member
        c1 = Client([rpc.addr])
        c2 = Client([rpc.addr])
        owner = LeasingKV(c1, "_leases/")
        writer = LeasingKV(c2, "_leases/")
        try:
            c1.put(b"rv", b"one")
            owner.get(b"rv")  # owner acquires + caches
            writer.put(b"rv", b"two")  # forces revocation
            wait_until(
                lambda: b"rv" not in owner._owned,
                msg="owner invalidated",
            )
            assert owner.get(b"rv").kvs[0].value == b"two"
        finally:
            owner.close()
            writer.close()
            c1.close()
            c2.close()


class TestNaming:
    def test_register_resolve_watch(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        eps = Endpoints(c, "services/db")
        eps.add("a", "10.0.0.1:2379")
        eps.add("b", "10.0.0.2:2379", metadata={"zone": "z1"})
        listing = eps.list()
        assert listing["a"]["Addr"] == "10.0.0.1:2379"
        assert listing["b"]["Metadata"]["zone"] == "z1"
        assert sorted(eps.addresses()) == ["10.0.0.1:2379", "10.0.0.2:2379"]
        h = eps.watch()
        eps.delete("a")
        got = h.get(timeout=5)
        assert got is not None
        h.cancel()
        assert "a" not in eps.list()
        c.close()


class TestOpenRangeSentinel:
    """etcd's range_end=\\x00 sentinel: 'every key >= key'
    (ref: rpc.proto RangeRequest doc)."""

    def test_get_all_keys(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        c.put(b"\x01low", b"a")
        c.put(b"zz\xff\xffhigh", b"b")
        resp = c.get(b"\x00", b"\x00")
        keys = [kv.key for kv in resp.kvs]
        assert b"\x01low" in keys
        assert b"zz\xff\xffhigh" in keys
        # From a midpoint: only keys >= that point.
        resp = c.get(b"zz", b"\x00")
        keys = [kv.key for kv in resp.kvs]
        assert b"zz\xff\xffhigh" in keys
        assert b"\x01low" not in keys
        c.close()

    def test_watch_all_keys(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        h = c.watch(b"\x00", b"\x00")
        c.put(b"anywhere/at/all", b"seen")
        got = h.get(timeout=5)
        assert got is not None
        assert got[1][0].kv.key == b"anywhere/at/all"
        h.cancel()
        c.close()

    def test_mirror_whole_keyspace(self, member, tmp_path):
        _, rpc = member
        from etcd_tpu.client.mirror import Syncer

        src = Client([rpc.addr])
        src.put(b"wm1", b"x")
        src.put(b"wm2", b"y")
        sy = Syncer(src)  # no prefix: everything
        rev, kvs = sy.sync_base()
        keys = [kv.key for kv in kvs]
        assert b"wm1" in keys and b"wm2" in keys
        src.close()


class TestSnapshotSave:
    def test_save_writes_file_atomically(self, member, tmp_path):
        _, rpc = member
        c = Client([rpc.addr])
        c.put(b"snapk", b"snapv")
        path = str(tmp_path / "c.snap.db")
        n = snapshot_save(c, path)
        assert n > 0
        assert os.path.getsize(path) == n
        assert not os.path.exists(path + ".part")
        c.close()


class TestAdvisorRegressions:
    """Round-1 advisor findings must stay fixed."""

    def test_mirror_streams_full_batches_at_max_txns_0(self, member):
        # A txn writing two keys produces ONE watch batch with two
        # events; with max_txns=0 (stream forever) both must be applied
        # (the old guard broke out of the batch after the first event).
        from etcd_tpu.client.mirror import Syncer

        _, rpc = member
        src = Client([rpc.addr])
        dest = Client([rpc.addr])
        src.put(b"mirr-src/seed", b"s")
        sy = Syncer(src, b"mirr-src/")
        stop = threading.Event()
        t = threading.Thread(
            target=lambda: sy.mirror_to(
                dest, dest_prefix=b"mirr-dst/", max_txns=0, stop=stop
            ),
            daemon=True,
        )
        t.start()
        time.sleep(0.3)  # let the update stream attach
        src.txn(sapi.TxnRequest(success=[
            sapi.RequestOp(request_put=sapi.PutRequest(
                key=b"mirr-src/a", value=b"1")),
            sapi.RequestOp(request_put=sapi.PutRequest(
                key=b"mirr-src/b", value=b"2")),
        ]))
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if (dest.get(b"mirr-dst/a").count
                    and dest.get(b"mirr-dst/b").count):
                break
            time.sleep(0.05)
        stop.set()
        t.join(timeout=5)
        assert dest.get(b"mirr-dst/a").kvs[0].value == b"1"
        assert dest.get(b"mirr-dst/b").kvs[0].value == b"2"
        src.close()
        dest.close()

    def test_revoke_stamp_keeps_owner_lease(self, member):
        # The REVOKE stamp must not detach the marker from the owner's
        # session lease (ignore_lease), or a dead owner's marker never
        # expires and writers block forever.
        _, rpc = member
        c1, c2 = Client([rpc.addr]), Client([rpc.addr])
        owner = LeasingKV(c1, "_rl/")
        owner.get(b"rlk")  # acquire marker bound to owner session lease
        marker = b"_rl/rlk"
        lease_before = c2.get(marker).kvs[0].lease
        assert lease_before == owner.session.lease_id
        # Simulate a dead owner: watcher gone, marker left behind.
        owner._closed = True
        owner._watch.cancel()
        owner._watcher.join(timeout=5)
        writer = LeasingKV(c2, "_rl/")
        with pytest.raises(TimeoutError):
            writer.put(b"rlk", b"w", timeout=1.0)
        kv = c2.get(marker).kvs[0]
        assert kv.value == b"REVOKE"
        assert kv.lease == lease_before, "REVOKE stamp detached the lease"
        # Owner's lease expiry (session close revokes) frees the writer.
        owner.session.close()
        writer.put(b"rlk", b"w2", timeout=5.0)
        assert c2.get(b"rlk").kvs[0].value == b"w2"
        writer.close()
        c1.close()
        c2.close()

    def test_cached_get_serves_acquisition_header(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        lkv = LeasingKV(c, "_rh/")
        c.put(b"rhk", b"v")
        first = lkv.get(b"rhk")
        assert first.kvs[0].value == b"v"
        cached = lkv.get(b"rhk")
        assert lkv.cache_hits >= 1
        assert cached.header.revision > 0
        lkv.close()
        c.close()

    def test_ordering_retries_once_after_remedy(self, member):
        _, rpc = member
        c = Client([rpc.addr])
        kv = OrderingKV(c)

        def remedy(_err):
            # Models switching to a caught-up endpoint.
            kv._prev_rev = 0

        kv.violation_fn = remedy
        kv.put(b"ord-r", b"x")
        kv._prev_rev = 10**9
        resp = kv.get(b"ord-r")  # violation -> remedy -> retried, no raise
        assert resp.kvs[0].value == b"x"
        c.close()
