"""Cross-hop request tracing (ISSUE 17): one request id from Router
ingress through a replica's batcher flush.

Four surfaces under test:

* **the header** — ``trace_header_value``/``parse_trace_header``
  roundtrip, garbled values degrade to ``(None, None)``, and the
  thread-local ``request_scope`` binds/restores exception-safely;
* **HTTP adoption** — a POST carrying ``X-Tftpu-Trace`` lands its
  request id on the replica's ``serving.request``/``serving.flush``
  spans and bumps ``tftpu_serving_request_trace_total``;
* **redrive stability** — the id IS the idempotency key: a crashed
  first attempt and its redrive carry the SAME id on the wire, and the
  router's ``router.request`` span joins the surviving replica's spans
  on it;
* **the merged-timeline acceptance** — a 2-process run (subprocess
  replica + in-process router, one ``TFTPU_RUN_ID``) merges into one
  Perfetto timeline where a single request id spans BOTH pids
  (subprocess pattern follows tests/test_trace_merge.py).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu.observability import context, events, merge
from tensorframes_tpu.serving import (
    Router,
    RouterConfig,
    Server,
    ServingConfig,
    serve_http,
)
from tensorframes_tpu.serving import metrics as sm

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
WIDTH = 4


def _schema(width=WIDTH):
    return tfs.Schema([
        tfs.ColumnInfo(
            "x", tfs.dtypes.float32, tfs.Shape((tfs.Unknown, width))
        )
    ])


def _program(width=WIDTH):
    holder = type("F", (), {"schema": _schema(width)})()
    return tfs.compile_program(
        lambda x: {"y": x * 2.0 + 1.0}, holder, block=False
    )


def _server(**cfg_kwargs) -> Server:
    cfg = dict(max_batch_rows=8, max_latency_s=0.002, max_queue_rows=128)
    cfg.update(cfg_kwargs)
    srv = Server(ServingConfig(**cfg))
    srv.register("score", _program())
    return srv


def _post(url, body=None, raw=None, headers=None, timeout=20):
    data = raw if raw is not None else json.dumps(body or {}).encode()
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=data, headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _spans(name, evs=None):
    """The events under ``name``: complete ("X") spans, and for a
    request lifetime — an async pair since ISSUE 25 — the "b" half
    (it carries the args) of every pair. A lifetime written as "X", or a
    pair missing a half, fails here."""
    if evs is None:
        evs = events.to_chrome_trace()["traceEvents"]
    mine = [e for e in evs if e.get("name") == name]
    if name.endswith(".request"):
        assert {e["ph"] for e in mine} <= {"b", "e"}, mine
        begins = [e for e in mine if e["ph"] == "b"]
        ends = {e["id"]: e for e in mine if e["ph"] == "e"}
        assert sorted(e["id"] for e in begins) == sorted(ends)
        for b in begins:
            e = ends[b["id"]]
            assert e["ts"] >= b["ts"] and e["cat"] == b["cat"]
            assert (e["pid"], e["tid"]) == (b["pid"], b["tid"])
        return begins
    return [e for e in mine if e.get("ph") == "X"]


@pytest.fixture
def _tracing():
    """Tracer on, drained before and after (other tests' spans must not
    leak into these assertions)."""
    was = events.TRACER.enabled
    events.clear()
    events.enable()
    yield
    events.clear()
    if not was:
        events.disable()


# ---------------------------------------------------------------------------
# the header + the thread-local scope
# ---------------------------------------------------------------------------

def test_trace_header_roundtrip_and_garble_tolerance():
    val = context.trace_header_value("req-abc123")
    rid, run = context.parse_trace_header(val)
    assert rid == "req-abc123"
    assert run == context.run_id()
    # degraded inputs: telemetry must never fail a request
    assert context.parse_trace_header(None) == (None, None)
    assert context.parse_trace_header("") == (None, None)
    assert context.parse_trace_header("x" * 300) == (None, None)
    assert context.parse_trace_header(";;;=") == (None, None)
    rid, run = context.parse_trace_header("just-an-id")
    assert rid == "just-an-id" and run is None
    rid, run = context.parse_trace_header("id;run=r1;extra=zz")
    assert rid == "id" and run == "r1"


def test_request_scope_binds_and_restores_per_thread():
    context.clear_request()
    assert context.current_request() is None
    with context.request_scope("outer"):
        assert context.current_request() == "outer"
        with context.request_scope("inner"):
            assert context.current_request() == "inner"
        assert context.current_request() == "outer"
        # exception-safe restore
        with pytest.raises(RuntimeError):
            with context.request_scope("doomed"):
                raise RuntimeError("boom")
        assert context.current_request() == "outer"
        # another thread sees ITS binding, not ours
        seen = []
        t = threading.Thread(target=lambda: seen.append(
            context.current_request()
        ))
        t.start()
        t.join()
        assert seen == [None]
    assert context.current_request() is None


# ---------------------------------------------------------------------------
# HTTP adoption: header → submit → batcher spans
# ---------------------------------------------------------------------------

def test_http_header_binds_request_id_onto_serving_spans(_tracing):
    srv = _server()
    srv.start()
    httpd = serve_http(srv)
    port = httpd.server_address[1]
    try:
        t0 = sm.REQUEST_TRACE.value
        status, body = _post(
            f"http://127.0.0.1:{port}/v1/score",
            {"inputs": {"x": [[1.0] * WIDTH]}},
            headers={context.TRACE_HEADER:
                     context.trace_header_value("req-http-1")},
        )
        assert status == 200, body
        np.testing.assert_allclose(
            np.asarray(body["outputs"]["y"]), [[3.0] * WIDTH]
        )
        assert sm.REQUEST_TRACE.value == t0 + 1
        reqs = [
            e for e in _spans("serving.request")
            if e["args"].get("request_id") == "req-http-1"
        ]
        assert len(reqs) == 1, (
            "the adopted id must ride the per-request span"
        )
        flushes = [
            e for e in _spans("serving.flush")
            if "req-http-1" in e["args"].get("request_ids", [])
        ]
        assert flushes, "the flush span lists the ids it served"

        # per-endpoint latency quantiles surfaced on stats() (satellite:
        # cardinality lives in the JSON body, NOT the registry — TFL003)
        lat = srv.stats()["latency"]
        assert "score" in lat
        assert {"p50", "p95", "p99"} <= set(lat["score"])
        assert 0.0 <= lat["score"]["p50"] <= lat["score"]["p99"]
    finally:
        httpd.shutdown()
        srv.stop(drain=True)


def test_submit_without_header_falls_back_to_idempotency_key(_tracing):
    srv = _server()
    srv.start()
    try:
        fut = srv.submit(
            "score", {"x": np.ones((1, WIDTH), np.float32)},
            idempotency_key="idem-7",
        )
        fut.result(10.0)
        ids = {
            e["args"].get("request_id")
            for e in _spans("serving.request")
        }
        assert "idem-7" in ids, (
            "an in-process submit that never touched the HTTP adapter "
            "must still be traceable by its idempotency key"
        )
    finally:
        srv.stop(drain=True)


# ---------------------------------------------------------------------------
# redrive: one id across both attempts, router ↔ replica spans join
# ---------------------------------------------------------------------------

class _HeaderRecordingCrasher:
    """A fake replica that records the trace header of every POST and
    then dies wordlessly — the crash-before-dispatch window."""

    def __init__(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        outer = self
        self.trace_headers = []

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_GET(self):
                body = json.dumps({
                    "state": "running", "running": True,
                    "queued_rows": {}, "endpoints": ["score"],
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                outer.trace_headers.append(
                    self.headers.get(context.TRACE_HEADER)
                )
                self.rfile.read(
                    int(self.headers.get("Content-Length", 0))
                )
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                self.close_connection = True

            def log_message(self, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        ).start()
        self.port = self.httpd.server_address[1]

    def stop(self):
        self.httpd.shutdown()


def test_redrive_keeps_one_request_id_across_attempts(_tracing):
    srv = _server()
    srv.start()
    httpd = serve_http(srv)
    real_port = httpd.server_address[1]
    crasher = _HeaderRecordingCrasher()
    router = Router(
        replicas={0: f"127.0.0.1:{crasher.port}",
                  1: f"127.0.0.1:{real_port}"},
        config=RouterConfig(poll_s=0.05),
    )
    router.start()
    try:
        deadline = time.monotonic() + 10.0
        while router.live_count() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.live_count() == 2
        # rank 0 (the crasher, load 0) wins the tie-break → attempt 1
        # crashes, the redrive lands on the real replica
        status, body = router.dispatch(
            "score", {"inputs": {"x": [[1.0] * WIDTH]}},
            deadline_s=20.0,
        )
        assert status == 200, body
        assert body["replica"] == 1

        # the wire: the crashed attempt carried a parseable header
        assert len(crasher.trace_headers) == 1
        rid0, run0 = context.parse_trace_header(crasher.trace_headers[0])
        assert rid0 and run0 == context.run_id()

        # the router's ingress span names the SAME id — stable across
        # the redrive because the id IS the idempotency key
        ingress = _spans("router.request")
        assert len(ingress) == 1
        assert ingress[0]["args"]["request_id"] == rid0
        assert ingress[0]["args"]["attempts"] == 2

        # ...and the surviving replica's span joins on it: the very
        # edge `observability merge` uses to stitch the timeline
        served = [
            e for e in _spans("serving.request")
            if e["args"].get("request_id") == rid0
        ]
        assert len(served) == 1
    finally:
        router.stop()
        crasher.stop()
        httpd.shutdown()
        srv.stop(drain=True)


# ---------------------------------------------------------------------------
# the acceptance: 2-process merged timeline, one id across both pids
# ---------------------------------------------------------------------------

# the replica process: serve one scoring endpoint over HTTP with the
# tracer on, write the bound port for the parent, save a shard when the
# parent signals done (file sentinel — the pattern works under any
# start method, unlike signals)
_REPLICA = """
import json, os, sys, time
import tensorframes_tpu as tfs
from tensorframes_tpu.observability import events
from tensorframes_tpu.serving import Server, ServingConfig, serve_http

shard_dir, port_file, done_file = sys.argv[1:4]
events.enable()
schema = tfs.Schema([
    tfs.ColumnInfo("x", tfs.dtypes.float32, tfs.Shape((tfs.Unknown, 4)))
])
holder = type("F", (), {"schema": schema})()
program = tfs.compile_program(
    lambda x: {"y": x * 2.0 + 1.0}, holder, block=False
)
srv = Server(ServingConfig(
    max_batch_rows=8, max_latency_s=0.002, max_queue_rows=128
))
srv.register("score", program)
srv.start()
httpd = serve_http(srv)
with open(port_file + ".tmp", "w") as f:
    f.write(str(httpd.server_address[1]))
os.replace(port_file + ".tmp", port_file)
deadline = time.monotonic() + 60.0
while not os.path.exists(done_file) and time.monotonic() < deadline:
    time.sleep(0.02)
httpd.shutdown()
srv.stop(drain=True)
path = events.save_shard(shard_dir)
print("SHARD", path, flush=True)
"""


@pytest.mark.slow
def test_two_process_trace_merges_with_one_request_id(tmp_path):
    run_id = "tracehop"
    shard_dir = str(tmp_path / "shards")
    os.makedirs(shard_dir)
    port_file = str(tmp_path / "port")
    done_file = str(tmp_path / "done")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TFTPU_RUN_ID"] = run_id
    env["TFTPU_PROCESS_INDEX"] = "1"
    proc = subprocess.Popen(
        [sys.executable, "-c", _REPLICA, shard_dir, port_file, done_file],
        env=env, cwd=_REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )

    saved_ctx = (context._run_id, context._process_index,
                 context._num_processes)
    context._reset_for_tests()
    context.bind(run_id=run_id, process_index=0)
    was_enabled = events.TRACER.enabled
    events.clear()
    events.enable()
    router = None
    try:
        deadline = time.monotonic() + 60.0
        while not os.path.exists(port_file):
            assert time.monotonic() < deadline, "replica never came up"
            assert proc.poll() is None, proc.communicate()[1]
            time.sleep(0.02)
        port = int(open(port_file).read())

        router = Router(
            replicas={1: f"127.0.0.1:{port}"},
            config=RouterConfig(poll_s=0.05),
        )
        router.start()
        while router.live_count() < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        status, body = router.dispatch(
            "score", {"inputs": {"x": [[1.0] * WIDTH]}},
            deadline_s=30.0,
        )
        assert status == 200, body
        router.stop()
        router = None

        ingress = _spans("router.request")
        assert len(ingress) == 1
        rid = ingress[0]["args"]["request_id"]
        events.save_shard(shard_dir)

        open(done_file, "w").close()
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, f"stdout: {out}\nstderr: {err}"
        assert "SHARD" in out
    finally:
        if router is not None:
            router.stop()
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
        events.clear()
        if not was_enabled:
            events.disable()
        context._reset_for_tests()
        context.bind(run_id=saved_ctx[0], process_index=saved_ctx[1],
                     num_processes=saved_ctx[2])

    shards = merge.find_shards(shard_dir, run_id=run_id)
    assert len(shards) == 2
    merged = json.loads(json.dumps(merge.merge_traces(shards)))
    evs = merged["traceEvents"]
    assert merged["otherData"]["run_id"] == run_id
    # ONE request id spans both processes: the router's ingress span on
    # pid 0 and the replica's serving spans on pid 1
    ingress = [
        e for e in _spans("router.request", evs)
        if e["args"].get("request_id") == rid
    ]
    served = [
        e for e in _spans("serving.request", evs)
        if e["args"].get("request_id") == rid
    ]
    assert len(ingress) == 1 and ingress[0]["pid"] == 0
    assert len(served) == 1 and served[0]["pid"] == 1
    # the pairs share the request id as their async id: one request,
    # one track, across both processes
    assert ingress[0]["id"] == served[0]["id"] == rid
    flushes = [
        e for e in evs
        if e.get("name") == "serving.flush"
        and rid in e["args"].get("request_ids", [])
    ]
    assert len(flushes) == 1 and flushes[0]["pid"] == 1
