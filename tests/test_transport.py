"""The front door + process fleet tier-1 suite: HTTP status mapping for
every shed reason (429/503 + Retry-After), deadline sheds at admission
vs at dispatch (504, never executed), W3C traceparent riding the socket
into the journal, torn-frame fault scoping at the transport point, the
retrying HTTP client honoring Retry-After, and the process-replica
fleet: SIGKILL -> typed replica_lost -> zero-compile respawn (excache
counters asserted) with the fleet ledger balanced across the episode.

The sustained-RPS socket scenario with a mid-traffic SIGKILL is
`make fleetnet-smoke` (tools/fleetnet_smoke.py); this suite pins the
contracts piece by piece.
"""
import http.client
import json
import os
import signal
import time
from concurrent.futures import Future

import numpy as np
import pytest

from deep_vision_tpu.obs import RunJournal, propagate, read_journal
from deep_vision_tpu.obs.registry import Registry
from deep_vision_tpu.resilience import faults
from deep_vision_tpu.serve import (
    DEADLINE_HEADER,
    SHED_REASONS,
    STATUS_BY_REASON,
    TRANSPORT_OUTCOMES,
    DeadlineExceeded,
    Engine,
    ProcReplicaPool,
    ReplicaLost,
    Server,
    ShedError,
    Transport,
)

IMG = (4, 4, 1)


def toy_fn(variables, images):
    flat = images.reshape((images.shape[0], -1))
    return {"scores": flat @ variables["w"]}


def toy_variables(scale=1.0, seed=0):
    import jax.numpy as jnp

    w = np.random.RandomState(seed).randn(16, 3).astype(np.float32) * scale
    return {"w": jnp.asarray(w)}


def an_image(seed=1):
    return np.random.RandomState(seed).rand(*IMG).astype(np.float32)


class FakeBackend:
    """In-memory backend: records calls + ambient trace context, answers
    instantly (or with the exception the test arms)."""

    def __init__(self, fail_with=None):
        self.calls = []
        self.ctxs = []
        self.fail_with = fail_with

    def submit(self, model, image, deadline_ms=None):
        self.calls.append((model, deadline_ms))
        self.ctxs.append(propagate.current())
        fut = Future()
        if self.fail_with is not None:
            fut.set_exception(self.fail_with)
        else:
            fut.set_result({"scores": [1.0, 2.0, 3.0]})
        return fut


class StubAdmission:
    """admit() answers from a scripted reason list (None = admitted)."""

    def __init__(self, reasons):
        self.reasons = list(reasons)
        self.depths = []

    def admit(self, model, queue_depth):
        self.depths.append(queue_depth)
        return self.reasons.pop(0) if self.reasons else None


def post(port, path, body, headers=None, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path,
                     body=body if isinstance(body, bytes)
                     else json.dumps(body).encode("utf-8"),
                     headers=headers or {})
        r = conn.getresponse()
        raw = r.read()
        return r.status, {k.lower(): v for k, v in r.getheaders()}, \
            json.loads(raw) if raw else None
    finally:
        conn.close()


def get(port, path, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


@pytest.fixture
def registry():
    return Registry()


def make_transport(tmp_path, registry, backend=None, **kw):
    journal = RunJournal(os.path.join(str(tmp_path), "journal.jsonl"),
                        kind="serve")
    kw.setdefault("models", ["toy"])
    tp = Transport(backend or FakeBackend(), journal=journal,
                   registry=registry, **kw).start()
    return tp, journal


class TestStatusMapping:
    def test_every_shed_reason_maps_to_its_status(self, tmp_path, registry):
        # the contract table itself: 429 only for rate_limited, 503 for
        # the capacity/lifecycle sheds
        assert STATUS_BY_REASON == {"rate_limited": 429,
                                    "queue_full": 503, "draining": 503}
        assert set(STATUS_BY_REASON) == set(SHED_REASONS)
        tp, journal = make_transport(
            tmp_path, registry,
            admission=StubAdmission(list(SHED_REASONS)))
        try:
            img = an_image().tolist()
            for reason in SHED_REASONS:
                st, hdrs, payload = post(tp.port, "/v1/toy", {"image": img})
                assert st == STATUS_BY_REASON[reason], (reason, payload)
                assert payload["reason"] == reason
                assert payload["retryable"] is True
                # Retry-After rides EVERY shed: seconds, decimal form
                assert float(hdrs["retry-after"]) > 0
            st, _, payload = post(tp.port, "/v1/toy", {"image": img})
            assert st == 200  # script exhausted: admitted
        finally:
            tp.close()
            journal.close()
        led = tp.ledger()
        assert led["shed"] == 3 and led["ok"] == 1 and led["balanced"]
        assert led["by_status"] == {"429": 1, "503": 2, "200": 1}
        evs = [e for e in read_journal(journal.path)
               if e.get("event") == "transport_request"]
        assert [e["outcome"] for e in evs] == ["shed"] * 3 + ["ok"]
        assert sorted(e["status"] for e in evs) == [200, 429, 503, 503]

    def test_backend_shed_maps_like_admission_shed(self, tmp_path,
                                                   registry):
        # a backend that runs its OWN admission (ReplicaPool raises
        # ShedError from submit) gets the same wire verdict
        class SheddingBackend(FakeBackend):
            def submit(self, model, image, deadline_ms=None):
                raise ShedError(model, "queue_full")

        tp, journal = make_transport(tmp_path, registry,
                                     backend=SheddingBackend())
        try:
            st, hdrs, payload = post(tp.port, "/v1/toy",
                                     {"image": an_image().tolist()})
            assert st == 503 and payload["reason"] == "queue_full"
            assert "retry-after" in hdrs
        finally:
            tp.close()
            journal.close()

    def test_replica_lost_is_503_retryable(self, tmp_path, registry):
        tp, journal = make_transport(
            tmp_path, registry,
            backend=FakeBackend(fail_with=ReplicaLost("p0 died")))
        try:
            st, hdrs, payload = post(tp.port, "/v1/toy",
                                     {"image": an_image().tolist()})
            assert st == 503 and payload["retryable"] is True
            assert "retry-after" in hdrs
            assert payload["error"] == "error"
        finally:
            tp.close()
            journal.close()

    def test_unknown_model_404_bad_body_400(self, tmp_path, registry):
        tp, journal = make_transport(tmp_path, registry)
        try:
            st, _, _ = post(tp.port, "/v1/nope",
                            {"image": an_image().tolist()})
            assert st == 404
            st, _, _ = post(tp.port, "/v1/toy", {"nope": 1})
            assert st == 400
            st, _, _ = post(tp.port, "/v1/toy", b"not json at all")
            assert st == 400
        finally:
            tp.close()
            journal.close()
        assert tp.ledger()["bad_request"] == 3 and tp.ledger()["balanced"]


class TestDeadline:
    def test_spent_budget_sheds_at_admission_backend_never_called(
            self, tmp_path, registry):
        backend = FakeBackend()
        tp, journal = make_transport(tmp_path, registry, backend=backend)
        try:
            st, _, payload = post(tp.port, "/v1/toy",
                                  {"image": an_image().tolist()},
                                  {DEADLINE_HEADER: "0.0001"})
            assert st == 504 and payload["stage"] == "admission"
            # shed means NOT EXECUTED: the backend never saw it
            assert backend.calls == []
        finally:
            tp.close()
            journal.close()
        assert tp.ledger()["deadline"] == 1

    def test_deadline_forwarded_to_backend(self, tmp_path, registry):
        backend = FakeBackend()
        tp, journal = make_transport(tmp_path, registry, backend=backend)
        try:
            st, _, _ = post(tp.port, "/v1/toy",
                            {"image": an_image().tolist()},
                            {DEADLINE_HEADER: "5000"})
            assert st == 200
            model, fwd = backend.calls[0]
            # the REMAINING budget rides to dispatch (shrunk by admission
            # overhead, never grown)
            assert fwd is not None and 0 < fwd <= 5000
        finally:
            tp.close()
            journal.close()

    def test_unparseable_deadline_header_is_400(self, tmp_path, registry):
        tp, journal = make_transport(tmp_path, registry)
        try:
            st, _, _ = post(tp.port, "/v1/toy",
                            {"image": an_image().tolist()},
                            {DEADLINE_HEADER: "soonish"})
            assert st == 400
        finally:
            tp.close()
            journal.close()

    def test_dispatch_pickup_past_deadline_sheds_504(self, tmp_path,
                                                     registry):
        # REAL router path: one request with a 5ms budget into a queue
        # whose max-wait is 80ms — the dispatcher picks it up past the
        # deadline and sheds it instead of executing (router counts it
        # an error; the wire sees 504 stage=dispatch)
        journal = RunJournal(os.path.join(str(tmp_path), "j.jsonl"),
                            kind="serve")
        eng = Engine(journal=journal, registry=registry)
        eng.register("toy", toy_fn, toy_variables(), input_shape=IMG,
                     buckets=(1, 2))
        eng.warmup()
        server = Server(eng, journal=journal, registry=registry,
                        max_wait_ms=80.0).start()
        tp = Transport(server, journal=journal, registry=registry).start()
        try:
            st, _, payload = post(tp.port, "/v1/toy",
                                  {"image": an_image().tolist()},
                                  {DEADLINE_HEADER: "5"})
            assert st == 504, payload
            assert payload["stage"] == "dispatch"
        finally:
            tp.close()
            server.drain("close")
            journal.close()
        assert tp.ledger()["deadline"] == 1
        evs = [e for e in read_journal(journal.path)
               if e.get("event") == "transport_request"]
        assert evs[0]["outcome"] == "deadline" and evs[0]["status"] == 504
        assert evs[0]["deadline_ms"] == 5.0


class TestTraceparent:
    def test_traceparent_rides_socket_into_journal_and_response(
            self, tmp_path, registry):
        backend = FakeBackend()
        tp, journal = make_transport(tmp_path, registry, backend=backend)
        ctx = propagate.new_trace()
        try:
            st, hdrs, _ = post(tp.port, "/v1/toy",
                               {"image": an_image().tolist()},
                               {"traceparent": ctx.to_traceparent()})
            assert st == 200
            # the response carries the server's span under the SAME trace
            echoed = propagate.from_traceparent(hdrs["traceparent"])
            assert echoed is not None
            assert echoed.trace_id == ctx.trace_id
            assert echoed.span_id != ctx.span_id
        finally:
            tp.close()
            journal.close()
        # the backend executed UNDER the propagated context...
        assert backend.ctxs[0] is not None
        assert backend.ctxs[0].trace_id == ctx.trace_id
        # ...and the journal event is linked to the caller's span
        evs = [e for e in read_journal(journal.path)
               if e.get("event") == "transport_request"]
        assert evs[0]["trace_id"] == ctx.trace_id
        assert evs[0]["parent_span_id"] == ctx.span_id

    def test_malformed_traceparent_starts_a_fresh_trace(self, tmp_path,
                                                        registry):
        tp, journal = make_transport(tmp_path, registry)
        try:
            st, hdrs, _ = post(tp.port, "/v1/toy",
                               {"image": an_image().tolist()},
                               {"traceparent": "00-garbage"})
            assert st == 200  # malformed context never fails a request
            assert propagate.from_traceparent(hdrs["traceparent"]) \
                is not None
        finally:
            tp.close()
            journal.close()


class TestTransportFaults:
    def teardown_method(self):
        faults.install(None)

    def test_torn_frame_fails_exactly_one_request(self, tmp_path,
                                                  registry):
        tp, journal = make_transport(tmp_path, registry)
        faults.install_spec("serve.transport:io_error@2", seed=3,
                            journal=journal, export_env=False)
        img = an_image().tolist()
        try:
            outcomes = []
            for _ in range(4):
                try:
                    st, _, _ = post(tp.port, "/v1/toy", {"image": img})
                    outcomes.append(st)
                except (http.client.HTTPException, OSError):
                    outcomes.append("torn")  # mid-frame reset: the
                    # connection dies without a response line
            assert outcomes == [200, "torn", 200, 200]
        finally:
            faults.install(None)
            tp.close()
            journal.close()
        led = tp.ledger()
        assert led["torn"] == 1 and led["ok"] == 3 and led["balanced"]
        evs = [e for e in read_journal(journal.path)
               if e.get("event") == "transport_request"
               and e.get("outcome") == "torn"]
        # status 0 = nothing hit the wire (check_journal allows it)
        assert len(evs) == 1 and evs[0]["status"] == 0

    def test_corrupt_frame_is_a_scoped_400(self, tmp_path, registry):
        tp, journal = make_transport(tmp_path, registry)
        faults.install_spec("serve.transport:corrupt@2", seed=3,
                            journal=journal, export_env=False)
        img = an_image().tolist()
        try:
            statuses = [post(tp.port, "/v1/toy", {"image": img})[0]
                        for _ in range(3)]
            assert statuses == [200, 400, 200]
        finally:
            faults.install(None)
            tp.close()
            journal.close()
        assert tp.ledger()["bad_request"] == 1

    def test_transport_is_a_registered_fault_point(self):
        assert "serve.transport" in faults.POINTS


class TestSchemaSync:
    def test_check_journal_knows_the_transport_schemas(self):
        from tools import check_journal as cj

        assert cj.EVENT_FIELDS["transport_request"] == (
            "status", "deadline_ms", "outcome")
        assert cj.EVENT_FIELDS["transport_server"] == (
            "host", "port", "outcome")
        assert cj.TRANSPORT_OUTCOMES == set(TRANSPORT_OUTCOMES)
        from deep_vision_tpu.serve.transport import \
            TRANSPORT_SERVER_OUTCOMES
        assert cj.TRANSPORT_SERVER_OUTCOMES == set(
            TRANSPORT_SERVER_OUTCOMES)

    def test_obs_report_without_transport_events_is_unchanged(self):
        from tools.obs_report import render, summarize_run

        events = [
            {"event": "run_manifest", "ts": 1.0, "run_id": "r",
             "kind": "serve", "argv": []},
            {"event": "serve_request", "ts": 2.0, "run_id": "r",
             "model": "toy", "latency_ms": 3.0, "outcome": "ok"},
            {"event": "exit", "ts": 3.0, "run_id": "r", "status": 0},
        ]
        summary = summarize_run(events)
        assert "fleet_edge" not in summary
        assert "fleet edge" not in render(summary)

    def test_obs_report_renders_the_fleet_edge(self):
        from tools.obs_report import render, summarize_run

        events = [
            {"event": "run_manifest", "ts": 1.0, "run_id": "r",
             "kind": "serve", "argv": []},
            {"event": "transport_server", "ts": 1.5, "run_id": "r",
             "host": "127.0.0.1", "port": 8080, "outcome": "started"},
            {"event": "transport_request", "ts": 2.0, "run_id": "r",
             "status": 200, "deadline_ms": 0.0, "outcome": "ok",
             "latency_ms": 3.0},
            {"event": "transport_request", "ts": 2.1, "run_id": "r",
             "status": 429, "deadline_ms": 0.0, "outcome": "shed",
             "latency_ms": 0.2, "reason": "rate_limited"},
            {"event": "transport_request", "ts": 2.2, "run_id": "r",
             "status": 504, "deadline_ms": 5.0, "outcome": "deadline",
             "latency_ms": 0.1, "stage": "dispatch"},
            {"event": "exit", "ts": 3.0, "run_id": "r", "status": 0},
        ]
        summary = summarize_run(events)
        edge = summary["fleet_edge"]
        assert edge["requests"]["by_status"] == {"200": 1, "429": 1,
                                                 "504": 1}
        assert edge["requests"]["balanced"] is True
        assert edge["deadline_stages"] == {"dispatch": 1}
        text = render(summary)
        assert "fleet edge" in text and "429x1" in text
        assert "deadline shed" in text and "dispatch=1" in text

    def test_knobs_registered(self):
        from deep_vision_tpu.core import knobs

        assert knobs.get_float("DVT_TRANSPORT_RETRY_AFTER_MS") > 0
        assert knobs.get_float("DVT_TRANSPORT_DEADLINE_MS") == 0.0


class TestHttpLoadClient:
    def test_client_honors_retry_after_and_recovers(self, tmp_path,
                                                    registry):
        from tools.loadgen import HttpLoadClient

        # shed twice, then admit: a retrying client must come back and
        # land the request, pacing itself by the server's Retry-After
        tp, journal = make_transport(
            tmp_path, registry,
            admission=StubAdmission(["rate_limited", "queue_full"]),
            retry_after_ms=30.0)
        client = HttpLoadClient("127.0.0.1", tp.port, registry=registry)
        try:
            row = client.submit("toy", an_image()).result(timeout=30)
            assert row["scores"] == [1.0, 2.0, 3.0]
        finally:
            client.close()
            tp.close()
            journal.close()
        assert client.counts["ok"] == 1
        assert client.counts["retries"] == 2
        assert client.counts["retry_after_honored"] >= 1
        led = tp.ledger()
        assert led["shed"] == 2 and led["ok"] == 1 and led["balanced"]

    def test_client_gives_up_typed_when_budget_exhausts(self, tmp_path,
                                                        registry):
        from deep_vision_tpu.resilience import RetryPolicy
        from tools.loadgen import HttpLoadClient

        tp, journal = make_transport(
            tmp_path, registry,
            admission=StubAdmission(["queue_full"] * 10),
            retry_after_ms=1.0)
        client = HttpLoadClient(
            "127.0.0.1", tp.port,
            retry=RetryPolicy(name="t", max_attempts=2, base_delay_s=0.001,
                              jitter=0.0, retry_on=(ShedError,)))
        try:
            with pytest.raises(ShedError):
                client.submit("toy", an_image()).result(timeout=30)
        finally:
            client.close()
            tp.close()
            journal.close()
        assert client.counts["shed"] == 1


class TestProcessFleet:
    """The real thing: spawned replica processes over real sockets."""

    def test_sigkill_respawn_zero_compiles_ledger_balances(
            self, tmp_path, registry):
        from tools.loadgen import fleet_builder

        work = str(tmp_path)
        journal = RunJournal(os.path.join(work, "journal.jsonl"),
                            kind="serve")
        pool = ProcReplicaPool(
            fleet_builder, replicas=2, run_dir=work,
            excache_dir=os.path.join(work, "excache"),
            journal=journal, registry=registry, heartbeat_s=0.4,
            ready_timeout_s=120.0)
        pool.start()
        try:
            # the parent's template paid the compiles and seeded the
            # cache; every CHILD warmed purely from it
            assert pool.template_warmup["backend_compiles"] > 0
            for rid, w in pool.warmup_stats().items():
                assert w["backend_compiles"] == 0, (rid, w)
                assert w["cache_hits"] == w["pairs"]

            img = an_image()
            for i in range(6):
                row = pool.submit("toy" if i % 2 else "aux",
                                  img).result(timeout=60)
            assert pool.ledger()["balanced"]

            # SIGKILL one replica with requests in flight: only ITS
            # in-flight window may fail, and the failures are typed
            victim = pool._slots["p0"]
            futs = [pool.submit("toy", img) for _ in range(8)]
            os.kill(victim.proc.pid, signal.SIGKILL)
            outcomes = {"ok": 0, "lost": 0}
            for fut in futs:
                try:
                    fut.result(timeout=60)
                    outcomes["ok"] += 1
                except ReplicaLost:
                    outcomes["lost"] += 1
            # the stream survived: the surviving replica answered its
            # share, and nothing failed UNTYPED
            assert outcomes["ok"] >= 1
            assert outcomes["ok"] + outcomes["lost"] == 8

            deadline = time.time() + 60
            while time.time() < deadline:
                if pool.replica_states()["p0"] == "serving" \
                        and victim.attempt == 2:
                    break
                time.sleep(0.1)
            assert victim.attempt == 2
            assert pool.replica_states()["p0"] == "serving"
            # rebirth was a disk read, not a compile
            assert pool.warmup_stats()["p0"]["backend_compiles"] == 0
            assert pool.submit("toy", img).result(timeout=60) is not None
        finally:
            summary = pool.drain("close")
            journal.close()
        assert summary["accepted"] == (summary["completed"]
                                       + summary["errors"]
                                       + summary["cancelled"])
        assert summary["pending"] == 0
        evs = read_journal(journal.path)
        losts = [e for e in evs if e.get("event") == "replica_lost"]
        recs = [e for e in evs if e.get("event") == "replica_recovered"]
        assert len(losts) == 1 and losts[0]["replica"] == "p0"
        assert len(recs) == 1 and recs[0]["attempt"] == 2
        # the excache counters IN THE JOURNAL: the respawned child's
        # warmup hit the cache for every pair and compiled nothing
        assert recs[0]["backend_compiles"] == 0
        assert recs[0]["cache_hits"] == recs[0]["pairs"] > 0

    def test_transport_fronts_the_process_fleet(self, tmp_path, registry):
        from tools.loadgen import fleet_builder

        work = str(tmp_path)
        journal = RunJournal(os.path.join(work, "journal.jsonl"),
                            kind="serve")
        pool = ProcReplicaPool(
            fleet_builder, replicas=2, run_dir=work,
            excache_dir=os.path.join(work, "excache"),
            journal=journal, registry=registry, heartbeat_s=0.4,
            ready_timeout_s=120.0)
        pool.start()
        tp = Transport(pool, journal=journal, registry=registry).start()
        ctx = propagate.new_trace()
        try:
            # one hop chain: client socket -> parent transport -> child
            # socket -> child transport, one trace end to end
            st, hdrs, payload = post(
                tp.port, "/v1/toy", {"image": an_image().tolist()},
                {"traceparent": ctx.to_traceparent(),
                 DEADLINE_HEADER: "30000"})
            assert st == 200 and "outputs" in payload
            st, health = get(tp.port, "/healthz")
            assert st == 200 and health["ok"] is True
            st, statusz = get(tp.port, "/statusz")
            assert st == 200
            assert statusz["telemetry_status"]["replicas"] == {
                "p0": "serving", "p1": "serving"}
        finally:
            tp.close()
            pool.drain("close")
            journal.close()
        assert tp.ledger()["ok"] == 1 and tp.ledger()["balanced"]
        # the trace crossed BOTH sockets: the parent's transport event
        # and the child's replica journal share the trace id
        evs = [e for e in read_journal(journal.path)
               if e.get("event") == "transport_request"]
        assert evs and evs[0]["trace_id"] == ctx.trace_id
        child_files = [p for p in os.listdir(work)
                       if p.startswith("replica-") and
                       p.endswith(".jsonl")]
        child_evs = []
        for p in child_files:
            child_evs += [e for e in read_journal(os.path.join(work, p))
                          if e.get("event") == "transport_request"]
        hops = [e for e in child_evs if e.get("trace_id") == ctx.trace_id]
        assert len(hops) == 1 and hops[0]["status"] == 200


class TestOneProcessPerChip:
    """On a TPU host a chip belongs to one process at a time: the pool
    parent stays off the chips, pins each child to its own, and refuses up
    front what cannot work — a child hanging on a held chip is the failure
    these refusals replace. (The pinned children themselves need the
    four-chip host; the chip run in CHANGES.md PR 21 is their proof.)"""

    def _pool(self, tmp_path, replicas, spawned):
        from deep_vision_tpu.serve import procpool
        from tools.loadgen import fleet_builder

        pool = procpool.ProcReplicaPool(fleet_builder, replicas=replicas,
                                        run_dir=str(tmp_path))
        pool._spawn = lambda slot, generation: spawned.append(slot)
        return pool

    def test_more_replicas_than_chips_is_refused_before_any_spawn(
            self, tmp_path, monkeypatch):
        from deep_vision_tpu.core import backend
        from deep_vision_tpu.serve.engine import ServeError

        monkeypatch.setattr(backend, "local_tpu_chips", lambda: 1)
        spawned = []
        with pytest.raises(ServeError, match="2 replicas .* 1 TPU chip"):
            self._pool(tmp_path, 2, spawned).start()
        assert spawned == []

    def test_a_parent_that_holds_the_chips_is_refused(
            self, tmp_path, monkeypatch):
        import jax

        from deep_vision_tpu.core import backend
        from deep_vision_tpu.serve.engine import ServeError

        jax.devices()  # this process HAS created a client
        assert backend.backend_initialized()
        monkeypatch.setattr(backend, "local_tpu_chips", lambda: 4)
        spawned = []
        with pytest.raises(ServeError, match="already initialised"):
            self._pool(tmp_path, 2, spawned).start()
        assert spawned == []

    def test_children_are_pinned_one_chip_each_and_no_template_is_built(
            self, tmp_path, monkeypatch):
        from deep_vision_tpu.core import backend
        from deep_vision_tpu.serve import procpool
        from deep_vision_tpu.serve.engine import ServeError

        monkeypatch.setattr(backend, "local_tpu_chips", lambda: 4)
        monkeypatch.setattr(backend, "backend_initialized", lambda: False)
        spawned = []
        pool = self._pool(tmp_path, 2, spawned)
        pool._wait_ready = lambda slot, deadline: None
        pool.start()
        try:
            assert [s.chip for s in spawned] == [0, 1]
            with pytest.raises(ServeError, match="no parent-side engine"):
                pool.primary_engine()
            with pytest.raises(ServeError, match="add_canary"):
                pool.add_canary(None, 10)
        finally:
            pool._stop.set()
        a, b = procpool._chip_env(0), procpool._chip_env(1)
        assert a["TPU_VISIBLE_CHIPS"] == "0" and b["TPU_VISIBLE_CHIPS"] == "1"
        assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert a["TPU_MESH_CONTROLLER_PORT"] != b["TPU_MESH_CONTROLLER_PORT"]

    def test_no_chips_are_counted_when_the_tpu_is_not_selected(self):
        from deep_vision_tpu.core import backend

        assert backend.local_tpu_chips() == 0  # conftest pins the CPU
