"""Round-10 observability tier: live-assembly /metrics exposition
validity (the CI gate that catches a malformed instrument the day it
lands), the /api/v1/debug/traces surface, hopwatch accounting, and the
``cli hops --check`` regression gate."""

import json
import urllib.request

import numpy as np
import pytest

from m3_tpu.instrument import exposition

BLOCK = 2 * 3600 * 10**9
START = (1_700_000_000 * 10**9) // BLOCK * BLOCK


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read().decode()


@pytest.fixture()
def assembly(tmp_path):
    from m3_tpu.server.assembly import run_node

    cfg = f"""
db:
  root: {tmp_path}
  namespaces:
    default: {{num_shards: 1}}
coordinator: {{listen_port: 0, tracing: true}}
mediator: {{enabled: false}}
"""
    asm = run_node(cfg)
    try:
        yield asm
    finally:
        asm.close()


def _write(port, n=8):
    t0 = START // 10**9
    samples = [{"tags": {"__name__": "obs", "i": str(i % 2)},
                "timestamp": t0 + i, "value": float(i)} for i in range(n)]
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/json/write",
        data=json.dumps(samples).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.load(r)["written"] == n


class TestLiveMetricsExposition:
    def test_metrics_parse_clean_under_strict_parser(self, assembly):
        """Tier-1 exposition gate: a live node's /metrics must satisfy
        the full text-format grammar — histogram ``le`` lanes ordered
        and cumulative, +Inf == _count, no duplicate series.  A new
        instrument that renders badly fails HERE, not on a dashboard."""
        port = assembly.port
        _write(port)
        # query + tick so the query/flush histograms have samples too
        _get(f"http://127.0.0.1:{port}/api/v1/query_range?query=obs"
             f"&start={START // 10**9}&end={START // 10**9 + 100}&step=10s")
        assembly.db.tick(START + BLOCK + 10**9)
        samples = exposition.parse_text(_get(
            f"http://127.0.0.1:{port}/metrics"))
        names = {s.name for s in samples}
        # the round-10 hot-path histograms are live on the scrape
        assert "m3tpu_ingest_seconds_bucket" in names
        assert "m3tpu_query_seconds_bucket" in names
        assert "m3tpu_db_tick_seconds_bucket" in names
        phases = {s.label("phase") for s in samples
                  if s.name == "m3tpu_query_phase_seconds_count"}
        assert phases == {"fetch", "eval"}

    def test_health_latency_section_is_windowed_histograms(self, assembly):
        port = assembly.port
        _write(port)
        health = json.loads(_get(f"http://127.0.0.1:{port}/health"))
        lat = health["latency"]
        (ingest_key,) = [k for k in lat if k.startswith("m3tpu.ingest")]
        s = lat[ingest_key]
        assert s["count"] >= 1 and "p50" in s and "p99" in s


class TestDebugTracesEndpoint:
    def test_inventory_by_trace_and_name_filter(self, assembly):
        """The span ring was write-only outside tests until round 10:
        the debug surface serves inventory, by-trace lookup (parent-
        before-child), and tracepoint-name filtering."""
        port = assembly.port
        _write(port)
        out = json.loads(_get(
            f"http://127.0.0.1:{port}/api/v1/debug/traces"))
        assert out["status"] == "success"
        inv = out["inventory"]
        assert inv, "no traces recorded for a traced write"
        row = max(inv, key=lambda r: r["spans"])
        assert "api.write" in row["names"]
        # by-trace lookup returns that trace's spans, parents first
        trace = json.loads(_get(
            f"http://127.0.0.1:{port}/api/v1/debug/traces"
            f"?trace_id={row['trace_id']}"))["data"]
        assert len(trace) == row["spans"]
        assert trace[0]["parent_id"] is None
        by_id = {s["span_id"] for s in trace}
        assert all(s["parent_id"] in by_id for s in trace[1:])
        # name filter
        only = json.loads(_get(
            f"http://127.0.0.1:{port}/api/v1/debug/traces"
            f"?name=api.write"))["data"]
        assert only and all(s["name"] == "api.write" for s in only)

    def test_admin_port_serves_the_same_ring(self, tmp_path):
        from m3_tpu.server.assembly import run_node

        cfg = f"""
db:
  root: {tmp_path}
  namespaces:
    default: {{num_shards: 1}}
coordinator: {{listen_port: 0, admin_listen_port: 0, tracing: true}}
mediator: {{enabled: false}}
"""
        asm = run_node(cfg)
        try:
            _write(asm.port)
            main = json.loads(_get(
                f"http://127.0.0.1:{asm.port}/api/v1/debug/traces"))
            admin = json.loads(_get(
                f"http://127.0.0.1:{asm.admin_port}/api/v1/debug/traces"))
            assert admin["status"] == "success"
            # same ring: identical span ids through either port (the
            # interpreter-lock probe adds a root about every 100 ms
            # while tracing is on: one may land between the two reads)
            def ids(doc):
                return {s["span_id"] for s in doc["data"]
                        if s["name"] != "runtime.gil.probe"}

            assert ids(admin) == ids(main) != set()
        finally:
            asm.close()

    def test_write_trace_stitches_api_to_db(self, assembly):
        port = assembly.port
        _write(port)
        out = json.loads(_get(
            f"http://127.0.0.1:{port}/api/v1/debug/traces"))
        traces = {}
        for s in out["data"]:
            traces.setdefault(s["trace_id"], []).append(s)
        stitched = [t for t in traces.values()
                    if {x["name"] for x in t} >= {"api.write",
                                                  "db.writeBatch"}]
        assert stitched, "api.write and db.writeBatch share no trace"
        t = stitched[0]
        root = [s for s in t if s["name"] == "api.write"][0]
        child = [s for s in t if s["name"] == "db.writeBatch"][0]
        assert child["parent_id"] == root["span_id"]


class TestDebugFaultsEndpoint:
    """Round-12: runtime faultpoint re-arm over HTTP — the chaos
    scheduler's window-flip surface, mirrored on the main and admin
    ports like debug/traces."""

    def _post(self, port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/debug/faults",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.load(r)

    def test_rearm_live_without_restart(self, tmp_path):
        from m3_tpu.server.assembly import run_node
        from m3_tpu.x import fault

        cfg = f"""
db:
  root: {tmp_path}
  namespaces:
    default: {{num_shards: 1}}
coordinator: {{listen_port: 0, admin_listen_port: 0}}
mediator: {{enabled: false}}
"""
        fault.disarm()
        fault.reset_counters()
        asm = run_node(cfg)
        try:
            # arm through the MAIN port: same grammar as M3_FAULTPOINTS
            out = self._post(asm.port, {
                "arm": "rpc.server=delay:ms=1:p=0.5:seed=4"})
            assert out["armed_count"] == 1
            assert out["armed"][0]["point"] == "rpc.server"
            # visible on the ADMIN port too (one process registry)
            admin = json.loads(_get(
                f"http://127.0.0.1:{asm.admin_port}/api/v1/debug/faults"))
            assert [s["mode"] for s in admin["armed"]] == ["delay"]
            # fire it, then RE-ARM: counters must survive the flip
            fault.fire("rpc.server")
            out = self._post(asm.admin_port, {
                "disarm": True, "arm": "rpc.server=drop:p=1.0"})
            assert [s["mode"] for s in out["armed"]] == ["drop"]
            assert out["counters"]["rpc.server.passes"] == 1
            # a malformed spec is a 400, and mutates NOTHING
            req = urllib.request.Request(
                f"http://127.0.0.1:{asm.port}/api/v1/debug/faults",
                data=b'{"arm": "broken-spec", "disarm": true}',
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=30)
                raise AssertionError("malformed spec must 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
            still = json.loads(_get(
                f"http://127.0.0.1:{asm.port}/api/v1/debug/faults"))
            assert [s["mode"] for s in still["armed"]] == ["drop"]
        finally:
            asm.close()
            fault.disarm()
            fault.reset_counters()


class TestIngestTracePreambleCompat:
    def test_legacy_server_degrades_to_untraced_delivery(self):
        """Review regression: a pre-round-10 ingest server kills the
        connection on the unknown INGEST_TRACE frame type.  The client
        must disable its preamble for that queue after the death and
        DELIVER the batch untraced — never spin in a reconnect loop."""
        import socketserver
        import threading

        from m3_tpu.client.aggregator_client import InstanceQueue
        from m3_tpu.instrument.tracing import Tracer
        from m3_tpu.msg import protocol as wire

        received = []

        class _LegacyHandler(socketserver.BaseRequestHandler):
            # round-9 server behavior: unknown frame -> drop the conn
            def handle(self):
                while True:
                    try:
                        frame = wire.recv_frame(self.request)
                    except (wire.ProtocolError, OSError):
                        return
                    if frame is None:
                        return
                    ftype, payload = frame
                    if ftype == wire.INGEST_HELLO:
                        continue
                    if ftype != wire.METRIC_BATCH:
                        return  # unknown frame: legacy break
                    batch = wire.decode_metric_batch(payload)
                    received.append(len(batch.ids))
                    wire.send_frame(self.request, wire.INGEST_ACK,
                                    wire.encode_ingest_ack(len(batch.ids)))

        srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0),
                                              _LegacyHandler)
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            q = InstanceQueue(srv.server_address, want_acks=True,
                              ack_timeout_s=5.0)
            tracer = Tracer()
            q.enqueue(3, b"m", 1.0, 1)
            with tracer.start_span("api.write"):
                # first flush: preamble kills the legacy conn; the
                # retrier redials, trips the disable, and delivers
                sent = q.flush()
                if sent == 0:  # all retries burned on the first probe
                    sent = q.flush()
            assert sent == 1
            assert q._trace_disabled
            assert received == [1]
            # subsequent sampled flushes stay untraced and deliver
            q.enqueue(3, b"m", 2.0, 2)
            with tracer.start_span("api.write"):
                assert q.flush() == 1
            q.close()
        finally:
            srv.shutdown()
            srv.server_close()


class TestHopwatch:
    def test_counts_attributed_to_hops(self):
        import jax
        import jax.numpy as jnp

        from m3_tpu.x import hopwatch

        hopwatch.install()
        try:
            hopwatch.reset()

            @jax.jit
            def f(x):
                return x * 2

            with hopwatch.hop("up"):
                a = jnp.asarray(np.ones((64, 64)))
            with hopwatch.hop("compute"):
                jax.block_until_ready(f(a))
            with hopwatch.hop("down"):
                np.asarray(f(a))
            st = hopwatch.stats()
            assert st["up"]["h2d_count"] == 1
            assert st["up"]["h2d_bytes"] == 64 * 64 * 8
            assert st["compute"]["dispatches"] == 1
            assert st["compute"]["compiles"] >= 1
            assert st["down"]["d2h_count"] == 1
            assert st["down"]["d2h_bytes"] == 64 * 64 * 8
            assert st["down"]["dispatches"] == 1  # the second f(a) call
            tot = hopwatch.totals()
            assert tot["h2d_count"] == 1 and tot["d2h_count"] == 1
        finally:
            hopwatch.uninstall()

    def test_snapshot_delta(self):
        import jax.numpy as jnp

        from m3_tpu.x import hopwatch

        hopwatch.install()
        try:
            hopwatch.reset()
            snap = hopwatch.snapshot()
            jnp.asarray(np.zeros(16))
            d = hopwatch.since(snap)
            assert d["h2d_count"] == 1 and d["h2d_bytes"] == 128
            assert d["d2h_count"] == 0
        finally:
            hopwatch.uninstall()

    def test_uninstall_restores_seams(self):
        import jax
        import numpy as onp

        from m3_tpu.x import hopwatch

        before = (jax.device_get, onp.asarray)
        hopwatch.install()
        assert (jax.device_get, onp.asarray) != before
        hopwatch.uninstall()
        assert (jax.device_get, onp.asarray) == before


class TestHopsCheckGate:
    def _artifact(self, bytes_steady, compiles_steady=0, dispatches=None):
        return {
            "pipeline": {"transfer_bytes_steady": bytes_steady,
                         "compiles_steady": compiles_steady},
            "hops": ({h: {"dispatches": d} for h, d in dispatches.items()}
                     if dispatches else {}),
        }

    def test_within_tolerance_passes(self, tmp_path):
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps(self._artifact(1000)))
        assert check_against_baseline(
            self._artifact(1200), str(base), tolerance=0.25) == []

    def test_transfer_regression_fails(self, tmp_path):
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps(self._artifact(1000)))
        errs = check_against_baseline(
            self._artifact(1300), str(base), tolerance=0.25)
        assert errs and "transfer bytes regressed" in errs[0]

    def test_steady_compile_regression_fails(self, tmp_path):
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps(self._artifact(1000, 0)))
        errs = check_against_baseline(
            self._artifact(1000, 2), str(base))
        assert errs and "compiles regressed" in errs[0]

    def test_dispatch_regression_fails(self, tmp_path):
        """The round-13 per-hop dispatch gate: a hop splitting into
        more device programs fails --check even when transfer bytes
        and compiles are flat (the leading indicator the transfer
        gate misses)."""
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps(
            self._artifact(1000, dispatches={"window_drain": 198})))
        errs = check_against_baseline(
            self._artifact(1000, dispatches={"window_drain": 240}),
            str(base), dispatch_tolerance=0.10)
        assert errs and "dispatches regressed" in errs[0]
        # within tolerance passes; a zero-dispatch hop gaining ANY fails
        assert check_against_baseline(
            self._artifact(1000, dispatches={"window_drain": 210}),
            str(base), dispatch_tolerance=0.10) == []
        base.write_text(json.dumps(
            self._artifact(1000, dispatches={"wire_parse": 0})))
        errs = check_against_baseline(
            self._artifact(1000, dispatches={"wire_parse": 1}), str(base))
        assert errs and "dispatches regressed" in errs[0]

    def test_missing_hop_fails(self, tmp_path):
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps(
            self._artifact(1000, dispatches={"encode": 1})))
        errs = check_against_baseline(self._artifact(1000), str(base))
        assert errs and "missing from this run" in errs[0]

    def test_dispatch_gate_reads_r09_nesting_too(self, tmp_path):
        """Back-compat: pre-r13 artifacts carry the count only inside
        the steady ledger — the gate must read both nestings."""
        from m3_tpu.tools.hops import check_against_baseline

        base = tmp_path / "PIPELINE.json"
        base.write_text(json.dumps({
            "pipeline": {"transfer_bytes_steady": 1000,
                         "compiles_steady": 0},
            "hops": {"window_drain": {"steady": {"dispatches": 100}}},
        }))
        errs = check_against_baseline(
            self._artifact(1000, dispatches={"window_drain": 150}),
            str(base))
        assert errs and "100 -> 150" in errs[0]

    @pytest.mark.parametrize("name", ["PIPELINE_r09.json",
                                      "PIPELINE_r13.json"])
    def test_committed_artifact_is_wellformed(self, name):
        from pathlib import Path

        art = json.loads(
            (Path(__file__).resolve().parent.parent / name).read_text())
        hops = art["hops"]
        assert set(hops) == {"wire_parse", "arena_ingest", "window_drain",
                             "encode", "fileset_write"}
        for h in hops.values():
            assert {"steady", "cold", "host_time_fraction", "transfers",
                    "bytes_moved"} <= set(h)
        assert art["pipeline"]["compiles_steady"] == 0
        assert art["findings"], "artifact must call out a host-hop finding"
        fracs = sum(h["host_time_fraction"] for h in hops.values())
        assert fracs == pytest.approx(1.0, abs=0.02)

    def test_committed_r13_carries_dispatch_fields(self):
        """The regenerated baseline has the first-class dispatch counts
        the new gate reads, and they agree with r09's steady ledger —
        the pipeline gained no dispatches across rounds 10-13."""
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        r13 = json.loads((root / "PIPELINE_r13.json").read_text())
        r09 = json.loads((root / "PIPELINE_r09.json").read_text())
        assert "dispatches_steady" in r13["pipeline"]
        for h, v in r13["hops"].items():
            assert "dispatches" in v
            assert v["dispatches"] == \
                r09["hops"][h]["steady"].get("dispatches", 0)
        assert r13["pipeline"]["dispatches_steady"] == sum(
            v["dispatches"] for v in r13["hops"].values())
