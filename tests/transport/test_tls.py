"""TLS on both channels (ref: tests/e2e tls variants,
client/pkg/transport/listener_test.go)."""

import socket
import threading
import time

import pytest

from etcd_tpu.client.client import Client, ClientError
from etcd_tpu.pkg.tlsutil import TLSInfo, self_cert
from etcd_tpu.raft.types import Message, MessageType
from etcd_tpu.transport.tcp import TCPTransport


@pytest.fixture(scope="module")
def certs(tmp_path_factory):
    # Strict verification fixture: one shared cert dir, so
    # hostname/CA checks are exercised (skip_verify=False).
    return self_cert(str(tmp_path_factory.mktemp("certs")), skip_verify=False)


def test_self_cert_generates_once(tmp_path):
    info = self_cert(str(tmp_path))
    info2 = self_cert(str(tmp_path))
    assert info.cert_file == info2.cert_file
    with open(info.cert_file) as f:
        assert "BEGIN CERTIFICATE" in f.read()


def test_peer_transport_tls_roundtrip(certs):
    """Two transports exchange raft messages over TLS."""
    got = []
    t1 = TCPTransport(member_id=1, cluster_id=7, tls_info=certs)
    t2 = TCPTransport(member_id=2, cluster_id=7, tls_info=certs)
    try:
        t2.register(2, got.append)
        t1.add_peer(2, t2.addr)
        m = Message(type=MessageType.MsgHeartbeat, to=2, from_=1, term=3)
        for _ in range(50):
            t1.send(1, [m])
            if got:
                break
            time.sleep(0.05)
        assert got and got[0].term == 3
    finally:
        t1.stop()
        t2.stop()


def test_plaintext_dial_to_tls_peer_rejected(certs):
    """A non-TLS dialer can't speak to a TLS peer listener."""
    got = []
    t2 = TCPTransport(member_id=2, cluster_id=7, tls_info=certs)
    t1 = TCPTransport(member_id=1, cluster_id=7)  # no TLS
    try:
        t2.register(2, got.append)
        t1.add_peer(2, t2.addr)
        t1.send(1, [Message(type=MessageType.MsgHeartbeat, to=2, from_=1)])
        time.sleep(0.5)
        assert not got
    finally:
        t1.stop()
        t2.stop()


def wait_ready(addr, certs, timeout=60.0):
    """The member serves: one TLS handshake and one answered request,
    tried again until both succeed."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            c = Client([addr], tls_info=certs)
            try:
                c.get(b"ready")
                return
            finally:
                c.close()
        except ClientError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.05)


class TestClientChannelTLS:
    @pytest.fixture
    def tls_cluster(self, tmp_path, certs):
        # Single member with a TLS RPC listener.
        from etcd_tpu.raftexample.transport import InProcNetwork
        from etcd_tpu.server import EtcdServer, ServerConfig
        from etcd_tpu.v3rpc.service import V3RPCServer

        srv = EtcdServer(ServerConfig(
            member_id=1, peers=[1], data_dir=str(tmp_path),
            network=InProcNetwork(), tick_interval=0.01))
        rpc = V3RPCServer(srv, bind=("127.0.0.1", 0), tls_info=certs)
        try:
            wait_ready(rpc.addr, certs)
            assert srv.is_leader()
            yield srv, rpc
        finally:
            rpc.stop()
            srv.stop()

    def test_tls_client_roundtrip(self, tls_cluster, certs):
        _, rpc = tls_cluster
        c = Client([rpc.addr], tls_info=certs)
        try:
            c.put(b"sk", b"sv")
            assert c.get(b"sk").kvs[0].value == b"sv"
        finally:
            c.close()

    def test_server_sends_no_session_tickets(self, tls_cluster, certs):
        """A ticket is read by the client's reader thread while another
        thread writes the first request: see ``server_context``."""
        _, rpc = tls_cluster
        c = Client([rpc.addr], tls_info=certs)
        try:
            c.get(b"sk")  # an answer read: a ticket would have come first
            assert c._sock.version() == "TLSv1.3"
            assert not c._sock.session.has_ticket
        finally:
            c.close()

    def test_plaintext_client_rejected(self, tls_cluster):
        _, rpc = tls_cluster
        with pytest.raises(ClientError):
            c = Client([rpc.addr], dial_timeout=1.0, request_timeout=2.0)
            try:
                c.get(b"x")
            finally:
                c.close()

    def test_wrong_ca_rejected(self, tls_cluster, tmp_path):
        _, rpc = tls_cluster
        other = self_cert(str(tmp_path / "other"), skip_verify=False)
        with pytest.raises(ClientError):
            Client([rpc.addr], tls_info=other, dial_timeout=1.0)

    def test_watch_over_tls(self, tls_cluster, certs):
        _, rpc = tls_cluster
        c = Client([rpc.addr], tls_info=certs)
        try:
            h = c.watch(b"wk")
            c.put(b"wk", b"wv")
            batch = h.get(timeout=10)
            assert batch is not None
            assert batch[1][0].kv.value == b"wv"
            h.cancel()
        finally:
            c.close()


def test_peer_auto_tls_distinct_certs_roundtrip(tmp_path):
    """The real --peer-auto-tls shape: every member has its OWN
    self-signed cert, so peer verification must be skipped (reference
    SelfCert sets InsecureSkipVerify; channel encrypted, not
    authenticated). Regression: strict verification here means no
    raft message ever crosses."""
    got = []
    t1 = TCPTransport(member_id=1, cluster_id=7,
                      tls_info=self_cert(str(tmp_path / "m1")))
    t2 = TCPTransport(member_id=2, cluster_id=7,
                      tls_info=self_cert(str(tmp_path / "m2")))
    try:
        t2.register(2, got.append)
        t1.add_peer(2, t2.addr)
        m = Message(type=MessageType.MsgHeartbeat, to=2, from_=1, term=9)
        for _ in range(50):
            t1.send(1, [m])
            if got:
                break
            time.sleep(0.05)
        assert got and got[0].term == 9
    finally:
        t1.stop()
        t2.stop()


def test_embed_auto_tls_cluster(tmp_path):
    """A 1-member embedded cluster with auto-TLS on both channels, the
    e2e shape of --auto-tls/--peer-auto-tls."""
    from etcd_tpu.embed import Config, start_etcd

    cfg = Config(
        name="m0",
        data_dir=str(tmp_path),
        listen_peer_urls="https://127.0.0.1:0",
        listen_client_urls="https://127.0.0.1:0",
        initial_cluster="m0=https://127.0.0.1:0",
        auto_tls=True,
        peer_auto_tls=True,
    )
    e = start_etcd(cfg)
    try:
        deadline = time.monotonic() + 20
        while not e.server.is_leader() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert e.server.is_leader()
        # The generated cert dir is trusted by construction.
        ca = cfg.client_tls_info()
        c = Client([e.rpc.addr], tls_info=ca)
        try:
            c.put(b"auto", b"tls")
            assert c.get(b"auto").kvs[0].value == b"tls"
        finally:
            c.close()
    finally:
        e.close()
