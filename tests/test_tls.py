"""Mutual TLS on the RPC and HTTP planes (VERDICT r4 missing item 1).

Reference: nomad/rpc.go:99-115 (every RPC conn wrapped in tls.Server),
helper/tlsutil/ (CA-pinned mutual verification), command/agent/http.go
(TLS HTTP listener), `nomad tls ca|cert create` workflow.
"""
import socket
import ssl

import pytest

from nomad_tpu import mock
from nomad_tpu.api.client import ApiClient, APIError
from nomad_tpu.api.http_server import HTTPAgentServer
from nomad_tpu.rpc.client import RpcClient
from nomad_tpu.rpc.server import RpcServer
from nomad_tpu.server.server import Server
from nomad_tpu.utils import tlsutil


@pytest.fixture(scope="module")
def pki(tmp_path_factory):
    pytest.importorskip("cryptography",
                        reason="PKI minting needs cryptography")
    return tlsutil.write_pki(str(tmp_path_factory.mktemp("pki")))


@pytest.fixture(scope="module")
def other_pki(tmp_path_factory):
    pytest.importorskip("cryptography",
                        reason="PKI minting needs cryptography")
    return tlsutil.write_pki(str(tmp_path_factory.mktemp("pki2")))


# ------------------------------------------------------------------ RPC
def test_rpc_mutual_tls_roundtrip(pki):
    srv = RpcServer(tls=tlsutil.server_context(
        pki["server.global.nomad"]))
    srv.register("Status.Ping", lambda params: {"pong": params})
    srv.start()
    try:
        cli = RpcClient(srv.addr, tls=tlsutil.client_context(
            pki["cli.global.nomad"]))
        assert cli.call("Status.Ping", [1, 2]) == {"pong": [1, 2]}
        cli.close()
    finally:
        srv.stop()


def test_rpc_rejects_plaintext_and_certless_clients(pki):
    srv = RpcServer(tls=tlsutil.server_context(
        pki["server.global.nomad"]))
    srv.register("Status.Ping", lambda params: "pong")
    srv.start()
    try:
        # 1. plaintext client: no handshake, no frames served
        plain = RpcClient(srv.addr)
        with pytest.raises(ConnectionError):
            plain.call("Status.Ping", [], timeout=3.0)
        plain.close()
        # 2. TLS client with NO certificate: handshake must fail
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.load_verify_locations(pki["ca"])
        ctx.check_hostname = False
        raw = socket.create_connection(srv.addr, timeout=3.0)
        with pytest.raises(ssl.SSLError):
            s = ctx.wrap_socket(raw)
            # some stacks surface the rejection on first read
            s.settimeout(3.0)
            if not s.recv(1):
                raise ssl.SSLError("connection closed by server")
        raw.close()
        # the server is still healthy for legitimate clients
        cli = RpcClient(srv.addr, tls=tlsutil.client_context(
            pki["cli.global.nomad"]))
        assert cli.call("Status.Ping", []) == "pong"
        cli.close()
    finally:
        srv.stop()


def test_rpc_rejects_cert_from_wrong_ca(pki, other_pki):
    srv = RpcServer(tls=tlsutil.server_context(
        pki["server.global.nomad"]))
    srv.register("Status.Ping", lambda params: "pong")
    srv.start()
    try:
        # client presents a cert minted by a DIFFERENT CA and pins that
        # CA for the server too — both directions must fail
        cli = RpcClient(srv.addr, tls=tlsutil.client_context(
            other_pki["cli.global.nomad"]))
        with pytest.raises(ConnectionError):
            cli.call("Status.Ping", [], timeout=3.0)
        cli.close()
    finally:
        srv.stop()


def test_two_node_cluster_over_mtls(pki):
    """A real two-server raft cluster with every RPC (raft heartbeats,
    appends, forwarding) over mutual TLS elects a leader and accepts a
    registration through a follower."""
    from nomad_tpu.rpc.endpoints import serve_cluster
    from nomad_tpu.client.sim import wait_until

    servers, server_rpcs, addrs = serve_cluster(
        n=2, num_workers=1,
        tls_server=tlsutil.server_context(pki["server.global.nomad"]),
        tls_client=tlsutil.client_context(pki["server.global.nomad"]))
    try:
        assert wait_until(lambda: any(s.is_leader() for s in servers),
                          timeout=20)
        job = mock.job()
        job.task_groups[0].count = 0
        from nomad_tpu.rpc.endpoints import RpcServerEndpoints
        eps = RpcServerEndpoints(
            list(addrs.values()),
            tls=tlsutil.client_context(pki["cli.global.nomad"]))
        eps.register_job(job)
        assert wait_until(lambda: any(
            s.store.job_by_id("default", job.id) is not None
            for s in servers), timeout=10)
        # a certless endpoint client cannot talk to the cluster at all
        plain = RpcServerEndpoints(list(addrs.values()))
        with pytest.raises((ConnectionError, Exception)):
            plain.register_job(mock.job())
    finally:
        for s in servers:
            s.stop()
        for r in server_rpcs:
            r.rpc.stop()


# ----------------------------------------------------------------- HTTP
@pytest.fixture(scope="module")
def https_agent(pki):
    server = Server(num_workers=1)
    server.start()
    http = HTTPAgentServer(server, None, port=0,
                           tls=pki["server.global.nomad"])
    http.start()
    yield server, http
    http.stop()
    server.stop()


def test_http_mutual_tls_roundtrip(pki, https_agent):
    server, http = https_agent
    assert http.address.startswith("https://")
    api = ApiClient(address=http.address,
                    tls=pki["cli.global.nomad"])
    jobs, _ = api.jobs.list()
    assert jobs == []


def test_http_rejects_certless_client(pki, https_agent):
    server, http = https_agent
    # https client that trusts the CA but presents NO cert
    import urllib.request
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.load_verify_locations(pki["ca"])
    ctx.check_hostname = False
    with pytest.raises((ssl.SSLError, OSError)):
        urllib.request.urlopen(f"{http.address}/v1/jobs", context=ctx,
                               timeout=5.0).read()
    # plain http client against the TLS port fails outright
    api = ApiClient(address=http.address.replace("https://", "http://"))
    with pytest.raises(APIError):
        api.jobs.list()


def test_cli_tls_ca_and_cert_create(tmp_path, capsys):
    pytest.importorskip("cryptography",
                        reason="PKI minting needs cryptography")
    from nomad_tpu.cli.main import main as cli_main
    assert cli_main(["tls", "ca", "create", "-d", str(tmp_path)]) == 0
    assert cli_main(["tls", "cert", "create", "-role",
                     "server.global.nomad", "-d", str(tmp_path)]) == 0
    cfg = tlsutil.TLSConfig(
        ca_file=str(tmp_path / "nomad-agent-ca.pem"),
        cert_file=str(tmp_path / "server.global.nomad.pem"),
        key_file=str(tmp_path / "server.global.nomad-key.pem"))
    assert cfg.enabled()
    # the minted material actually works end to end
    srv = RpcServer(tls=tlsutil.server_context(cfg))
    srv.register("Status.Ping", lambda params: "pong")
    srv.start()
    try:
        cli = RpcClient(srv.addr, tls=tlsutil.client_context(cfg))
        assert cli.call("Status.Ping", []) == "pong"
        cli.close()
    finally:
        srv.stop()


def test_agent_config_tls_stanza(tmp_path):
    from nomad_tpu.cli.config import parse_agent_config
    cfg = parse_agent_config('''
bind_addr = "127.0.0.1"
tls {
  http      = true
  rpc       = true
  ca_file   = "/pki/ca.pem"
  cert_file = "/pki/server.pem"
  key_file  = "/pki/server-key.pem"
}
''')
    assert cfg.tls_http and cfg.tls_rpc
    assert cfg.tls_ca_file == "/pki/ca.pem"
    tls = cfg.tls_config()
    assert tls is not None and tls.enabled()


# ------------------------------------------- certificate-role gating
def test_client_role_cert_rejected_from_server_verbs(pki):
    """ADVICE r5 item 1: with mTLS on, ANY CA-signed cert completes the
    handshake — but raft / server-to-server verbs must additionally
    require the server.<region>.nomad SAN role.  A client-role cert
    gets a typed permission_denied, while public verbs still work."""
    from nomad_tpu.rpc.client import RpcError

    srv = RpcServer(tls=tlsutil.server_context(
        pki["server.global.nomad"]), region="global")
    srv.register("Status.Ping", lambda params: "pong")
    srv.register("raft.rpc_request_vote", lambda params: "granted",
                 server_only=True)
    srv.start()
    try:
        # client-role cert: public verb ok, raft verb denied
        cli = RpcClient(srv.addr, tls=tlsutil.client_context(
            pki["client.global.nomad"]))
        assert cli.call("Status.Ping", []) == "pong"
        with pytest.raises(RpcError) as e:
            cli.call("raft.rpc_request_vote", [])
        assert e.value.kind == "permission_denied"
        cli.close()
        # server-role cert: raft verb allowed
        peer = RpcClient(srv.addr, tls=tlsutil.client_context(
            pki["server.global.nomad"]))
        assert peer.call("raft.rpc_request_vote", []) == "granted"
        peer.close()
    finally:
        srv.stop()


def test_verify_hostname_rejects_non_server_peer(pki):
    """RpcClient with verify_hostname set applies the post-handshake
    SAN role check: a listener presenting a client-role cert (an
    impersonating node) is rejected even though the CA pins."""
    # a "server" armed with a client-role certificate
    impostor = RpcServer(tls=tlsutil.server_context(
        pki["client.global.nomad"]))
    impostor.register("Status.Ping", lambda params: "pong")
    impostor.start()
    try:
        cli = RpcClient(impostor.addr,
                        tls=tlsutil.client_context(
                            pki["server.global.nomad"]),
                        verify_hostname="server.global.nomad")
        with pytest.raises(ConnectionError):
            cli.call("Status.Ping", [], timeout=3.0)
        cli.close()
        # without the pin the same dial succeeds (CA-only trust)
        lax = RpcClient(impostor.addr, tls=tlsutil.client_context(
            pki["server.global.nomad"]))
        assert lax.call("Status.Ping", []) == "pong"
        lax.close()
    finally:
        impostor.stop()


def test_two_node_cluster_role_gated_raft(pki):
    """serve_cluster with verify_hostname: raft still elects (server
    certs pass the gate both ways)."""
    import time as _time

    from nomad_tpu.rpc.endpoints import serve_cluster
    servers, server_rpcs, _addrs = serve_cluster(
        n=2, num_workers=0,
        tls_server=tlsutil.server_context(pki["server.global.nomad"]),
        tls_client=tlsutil.client_context(pki["server.global.nomad"]),
        verify_hostname="server.global.nomad")
    try:
        deadline = _time.time() + 10.0
        while _time.time() < deadline:
            if any(s.is_leader() for s in servers):
                break
            _time.sleep(0.05)
        assert any(s.is_leader() for s in servers), \
            "role-gated raft failed to elect"
    finally:
        for s in servers:
            s.stop()
        for r in server_rpcs:
            r.rpc.stop()
