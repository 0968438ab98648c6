"""WebDAV gateway + filer notification + benchmark CLI tests."""

import json
import threading
import time
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest
import requests

from seaweedfs_tpu.filer import Filer, MemoryStore
from seaweedfs_tpu.filer.notification import MqNotifier, WebhookNotifier
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.server.webdav_server import WebDavServer


from conftest import allocate_port as free_port


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("gw")
    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp / "v")],
        master=f"localhost:{mport}",
        ip="localhost",
        port=free_port(),
        ec_backend="cpu",
    )
    vs.start()
    while not master.topo.nodes:
        time.sleep(0.05)
    yield mport
    vs.stop()
    master.stop()


def test_webdav_crud_and_propfind(cluster):
    filer = Filer(MemoryStore(), master=f"localhost:{cluster}")
    port = free_port()
    srv = WebDavServer(filer, ip="localhost", port=port)
    srv.start()
    base = f"http://localhost:{port}"
    try:
        r = requests.request("OPTIONS", base + "/")
        assert "PROPFIND" in r.headers["Allow"]
        assert requests.request("MKCOL", f"{base}/docs").status_code == 201
        data = b"dav content" * 1000
        assert requests.put(f"{base}/docs/a.txt", data=data,
                            headers={"Content-Type": "text/plain"}).status_code == 201
        r = requests.get(f"{base}/docs/a.txt")
        assert r.content == data
        # PROPFIND depth 1 lists the collection
        r = requests.request("PROPFIND", f"{base}/docs", headers={"Depth": "1"})
        assert r.status_code == 207
        root = ET.fromstring(r.content)
        hrefs = [e.text for e in root.iter("{DAV:}href")]
        assert "/docs/" in hrefs and "/docs/a.txt" in hrefs
        sizes = [e.text for e in root.iter("{DAV:}getcontentlength")]
        assert str(len(data)) in sizes
        # MOVE
        r = requests.request(
            "MOVE", f"{base}/docs/a.txt",
            headers={"Destination": f"{base}/docs/b.txt"},
        )
        assert r.status_code == 201
        assert requests.get(f"{base}/docs/b.txt").content == data
        assert requests.get(f"{base}/docs/a.txt").status_code == 404
        # COPY
        r = requests.request(
            "COPY", f"{base}/docs/b.txt",
            headers={"Destination": f"{base}/docs/c.txt"},
        )
        assert r.status_code == 201
        assert requests.get(f"{base}/docs/c.txt").content == data
        # same-path MOVE is forbidden and must not destroy the file
        r = requests.request(
            "MOVE", f"{base}/docs/b.txt",
            headers={"Destination": f"{base}/docs/b.txt"},
        )
        assert r.status_code == 403
        assert requests.get(f"{base}/docs/b.txt").content == data
        # Overwrite: F protects an existing destination
        r = requests.request(
            "MOVE", f"{base}/docs/b.txt",
            headers={"Destination": f"{base}/docs/c.txt", "Overwrite": "F"},
        )
        assert r.status_code == 412
        assert requests.get(f"{base}/docs/c.txt").content == data
        # chunked PUT (no Content-Length)
        def gen():
            yield b"chunked "
            yield b"body"
        r = requests.put(f"{base}/docs/chunked.txt", data=gen())
        assert r.status_code == 201
        assert requests.get(f"{base}/docs/chunked.txt").content == b"chunked body"
        # percent-encoded hrefs for awkward names
        requests.put(f"{base}/docs/a%20b%23c.txt", data=b"x")
        r = requests.request("PROPFIND", f"{base}/docs", headers={"Depth": "1"})
        assert "/docs/a%20b%23c.txt" in r.text
        # DELETE collection
        assert requests.delete(f"{base}/docs").status_code == 204
        assert requests.get(f"{base}/docs/b.txt").status_code == 404
    finally:
        srv.stop()
        filer.close()


def test_webhook_notifier(cluster):
    received = []

    class Hook(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", "0"))
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    hport = free_port()
    hook_srv = ThreadingHTTPServer(("localhost", hport), Hook)
    threading.Thread(target=hook_srv.serve_forever, daemon=True).start()

    filer = Filer(MemoryStore(), master=f"localhost:{cluster}")
    notifier = WebhookNotifier(f"http://localhost:{hport}/events")
    filer.subscribe(notifier)
    try:
        filer.write_file("/n/x.bin", b"notify me")
        filer.delete_entry("/n/x.bin")
        # mkdir + create + delete. `delivered` is bumped only after the
        # hook's response returns, so wait on it, not on `received`; the
        # deadline has to survive six busy test workers.
        deadline = time.time() + 30
        while notifier.delivered < 3 and time.time() < deadline:
            time.sleep(0.05)
        assert notifier.delivered >= 3
        creates = [e for e in received if e["newEntry"] and e["newEntry"]["name"] == "x.bin"]
        deletes = [e for e in received if e["oldEntry"] and not e["newEntry"]]
        assert creates and deletes
        assert creates[0]["directory"] == "/n"
    finally:
        notifier.close()
        hook_srv.shutdown()
        hook_srv.server_close()
        filer.close()


def test_mq_notifier(cluster):
    from seaweedfs_tpu.mq import MqBrokerServer, MqClient

    broker = MqBrokerServer(ip="localhost", grpc_port=free_port())
    broker.start()
    filer = Filer(MemoryStore(), master=f"localhost:{cluster}")
    notifier = MqNotifier(f"localhost:{broker.grpc_port}")
    filer.subscribe(notifier)
    try:
        filer.write_file("/mq/y.bin", b"event")
        c = MqClient(f"localhost:{broker.grpc_port}")
        # The notifier publishes asynchronously; poll with a deadline
        # instead of a one-shot read (the one-shot raced delivery).
        deadline = time.monotonic() + 10.0
        found = False
        while not found and time.monotonic() < deadline:
            events = []
            for p in range(4):
                for rec in c.subscribe("filer-events", p, start_offset=0):
                    events.append(json.loads(rec.message.value))
            found = any(
                e["newEntry"] and e["newEntry"]["name"] == "y.bin"
                for e in events
            )
            if not found:
                time.sleep(0.05)
        c.close()
        assert found
    finally:
        notifier.close()
        filer.close()
        broker.stop()


def test_benchmark_cli(cluster):
    from seaweedfs_tpu.benchmark.__main__ import main as bench_main

    assert bench_main(
        ["-master", f"localhost:{cluster}", "-n", "40", "-size", "500", "-c", "4"]
    ) == 0


def test_webdav_class2_locks(cluster):
    """RFC 4918 class 2: LOCK/UNLOCK with If-token enforcement,
    refresh, depth-infinity collection locks, unmapped-URL creation."""
    from seaweedfs_tpu.server.webdav_server import WebDavServer

    filer = Filer(MemoryStore(), master=f"localhost:{cluster}")
    dav = WebDavServer(filer, ip="localhost", port=free_port())
    dav.start()
    base = f"http://localhost:{dav.port}"
    try:
        opts = requests.options(f"{base}/")
        assert "2" in opts.headers["DAV"]
        assert "LOCK" in opts.headers["Allow"]

        lockinfo = (
            '<?xml version="1.0"?><D:lockinfo xmlns:D="DAV:">'
            "<D:lockscope><D:exclusive/></D:lockscope>"
            "<D:locktype><D:write/></D:locktype>"
            "<D:owner>alice</D:owner></D:lockinfo>"
        )
        # LOCK on an unmapped URL creates the resource (201)
        r = requests.request(
            "LOCK", f"{base}/doc.txt", data=lockinfo,
            headers={"Timeout": "Second-60"},
        )
        assert r.status_code == 201, r.status_code
        token = r.headers["Lock-Token"].strip("<>")
        assert token.startswith("opaquelocktoken:")
        assert "lockdiscovery" in r.text

        # mutations without the token are 423; with it they pass
        assert requests.put(f"{base}/doc.txt", data=b"x").status_code == 423
        assert requests.delete(f"{base}/doc.txt").status_code == 423
        r = requests.put(
            f"{base}/doc.txt", data=b"locked write",
            headers={"If": f"(<{token}>)"},
        )
        assert r.status_code == 201
        assert requests.get(f"{base}/doc.txt").content == b"locked write"

        # second LOCK on the same resource conflicts
        r2 = requests.request("LOCK", f"{base}/doc.txt", data=lockinfo)
        assert r2.status_code == 423

        # refresh (empty body + If header)
        r3 = requests.request(
            "LOCK", f"{base}/doc.txt",
            headers={"If": f"(<{token}>)", "Timeout": "Second-120"},
        )
        assert r3.status_code == 200 and "Second-120" in r3.text

        # PROPFIND shows the active lock
        pf = requests.request(
            "PROPFIND", f"{base}/doc.txt", headers={"Depth": "0"}
        )
        assert "lockdiscovery" in pf.text and "supportedlock" in pf.text

        # UNLOCK frees it
        assert (
            requests.request(
                "UNLOCK", f"{base}/doc.txt",
                headers={"Lock-Token": f"<{token}>"},
            ).status_code
            == 204
        )
        assert requests.put(f"{base}/doc.txt", data=b"free").status_code == 201

        # depth-infinity collection lock protects children
        requests.request("MKCOL", f"{base}/proj")
        r = requests.request("LOCK", f"{base}/proj", data=lockinfo)
        assert r.status_code == 200
        ctoken = r.headers["Lock-Token"].strip("<>")
        assert (
            requests.put(f"{base}/proj/child.txt", data=b"y").status_code
            == 423
        )
        assert (
            requests.put(
                f"{base}/proj/child.txt", data=b"y",
                headers={"If": f"(<{ctoken}>)"},
            ).status_code
            == 201
        )
        # a MOVE of a locked subtree without the token is refused
        requests.put(f"{base}/other.txt", data=b"z")
        assert (
            requests.request(
                "MOVE", f"{base}/proj/child.txt",
                headers={"Destination": f"{base}/elsewhere.txt"},
            ).status_code
            == 423
        )
    finally:
        dav.stop()
        filer.close()


def test_kafka_notifier(cluster):
    """Filer events flow to a Kafka-protocol broker (the reference's
    weed/notification/kafka sink) and are consumable with any client."""
    from seaweedfs_tpu.filer.notification import make_notifier
    from seaweedfs_tpu.mq.broker import MqBrokerServer
    from seaweedfs_tpu.mq.kafka.client import KafkaClient

    broker = MqBrokerServer(
        ip="localhost", grpc_port=free_port(), kafka_port=0,
        archive_interval=0,
    )
    broker.start()
    filer = Filer(MemoryStore(), master=f"localhost:{cluster}")
    notifier = make_notifier(
        "kafka", f"localhost:{broker.kafka.port}", topic="filer-ev"
    )
    filer.subscribe(notifier)
    try:
        filer.write_file("/kn/z.bin", b"kafka event")
        c = KafkaClient("127.0.0.1", broker.kafka.port)
        deadline = time.monotonic() + 10
        found = False
        while not found and time.monotonic() < deadline:
            _, recs = c.fetch("filer-ev", 0, 0)
            for r in recs:
                ev = json.loads(r.value)
                if ev.get("newEntry") and ev["newEntry"]["name"] == "z.bin":
                    found = True
            if not found:
                time.sleep(0.05)
        c.close()
        assert found
    finally:
        notifier.close()
        filer.close()
        broker.stop()


def test_gated_cloud_sinks_fail_loudly():
    from seaweedfs_tpu.filer.notification import make_notifier

    import pytest as _pytest

    with _pytest.raises((RuntimeError, NotImplementedError)):
        make_notifier("sqs", "https://sqs.region.amazonaws.com/q")
    with _pytest.raises((RuntimeError, NotImplementedError)):
        make_notifier("pubsub", "projects/p/topics/t")
    with _pytest.raises(ValueError):
        make_notifier("bogus", "x")
