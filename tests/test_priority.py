"""SLO-aware continuous batching (ISSUE 10): priority lanes, deadline
scheduling, and admission control. Covers the schema stamp (priority +
deadline side channel, typed expired results), broker lane partitioning
on BOTH backends (lane-ordered XREADGROUP/XCLAIM, XSHED admission
flags), the client fast-fail on shed, the engine's weighted-deficit lane
schedule with starvation protection, max-wait partial-bucket dispatch,
deadline-slack dispatch, deadline-expiry accounting, the lane/lease
interplay (a dead replica's interactive entries reclaim before its
batch-lane entries — SIGKILL variant slow-marked for the scheduling
lane), the admission-control flip, the frontend's lane state + typed
429/504 answers, and the zero-silent-drops ledger."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common import resilience, slo, telemetry
from analytics_zoo_tpu.serving import (
    Broker, ClusterServing, FrontEnd, InputQueue, OutputQueue,
)
from analytics_zoo_tpu.serving import schema
from analytics_zoo_tpu.serving.broker import (
    BrokerClient, ShedError, build_native_broker,
)
from analytics_zoo_tpu.serving.engine import _parse_lane_map


BACKENDS = ["python"] + (["native"] if build_native_broker() else [])

STREAM, GROUP = "serving_stream", "serving"
LANES = ",".join(schema.PRIORITIES)


@pytest.fixture(params=BACKENDS)
def broker(request):
    b = Broker.launch(backend=request.param)
    yield b
    b.stop()


@pytest.fixture(autouse=True)
def _fresh_slo_monitor():
    """Every test starts with a fresh lazily-created SLO monitor: burn
    windows baseline at the test's first tick instead of inheriting the
    multi-second latencies earlier tests fed the process-global
    histograms (a stalled-replica drill would otherwise trip admission
    control in whatever test runs after it)."""
    slo.set_monitor(None)
    yield
    slo.set_monitor(None)


def _counter(family, label=None):
    """Current value of a registry counter from the global snapshot (0.0
    when the family has never been touched)."""
    fam = telemetry.snapshot().get(family, {})
    if not isinstance(fam, dict):
        return float(fam or 0.0)
    if label is None:
        return float(next(iter(fam.values()), 0.0))
    return float(fam.get(label, 0.0))


# ------------------------------------------------------- schema side channel

class TestSchema:
    def test_validate_priority(self):
        assert schema.validate_priority(None) == schema.DEFAULT_PRIORITY
        for lane in schema.PRIORITIES:
            assert schema.validate_priority(lane) == lane
        with pytest.raises(ValueError):
            schema.validate_priority("urgent")

    def test_trace_stamp_carries_priority_and_deadline(self):
        trace = {"id": "r1", "t_pc": 1.0, "t_wall": 2.0, "s": 0,
                 "p": "interactive", "d": 250.0}
        payload = schema.encode_record(
            "r1", {"x": np.zeros(3, np.float32)}, None, trace=trace)
        uri, inputs, meta = schema.decode_record_meta(payload)
        assert uri == "r1" and set(inputs) == {"x"}
        assert meta["p"] == "interactive" and meta["d"] == 250.0

    def test_expired_result_is_typed(self):
        exp = schema.encode_error("deadline lapsed", None, code="expired")
        with pytest.raises(schema.DeadlineExpiredError):
            schema.decode_result(exp)
        # DeadlineExpiredError IS a ServingError — callers catching the
        # generic family still see expired records
        assert issubclass(schema.DeadlineExpiredError, schema.ServingError)
        plain = schema.encode_error("model exploded", None)
        with pytest.raises(schema.ServingError) as ei:
            schema.decode_result(plain)
        assert not isinstance(ei.value, schema.DeadlineExpiredError)


# ------------------------------------------- broker lanes, both backends

class TestBrokerLanes:
    def test_lane_ordered_read_and_per_lane_xlen(self, broker):
        c = broker.client()
        # arrival order is the REVERSE of priority order
        c.xadd("s", "YjA=", lane="batch")
        c.xadd("s", "YjE=", lane="batch")
        c.xadd("s", "ZDA=", lane="default")
        c.xadd("s", "aTA=", lane="interactive")
        assert c.xlen("s") == 4
        assert c.xlen("s", "interactive") == 1
        assert c.xlen("s", "default") == 1
        assert c.xlen("s", "batch") == 2
        got = c.xreadgroup("g", "c0", "s", 10, lanes=LANES)
        # 3-tuples, drained in lane-priority order, FIFO within a lane
        assert [(lane, payload) for _, lane, payload in got] == [
            ("interactive", "aTA="), ("default", "ZDA="),
            ("batch", "YjA="), ("batch", "YjE=")]

    def test_laneless_read_is_back_compatible(self, broker):
        c = broker.client()
        c.xadd("s", "YQ==", lane="batch")
        c.xadd("s", "Yg==")                    # legacy laneless enqueue
        got = c.xreadgroup("g", "c0", "s", 10)
        # legacy 2-tuple shape, arrival order across all lanes
        assert got == [(1, "YQ=="), (2, "Yg==")]

    def test_xshed_flag_rejects_xadd_on_that_lane_only(self, broker):
        c = broker.client()
        assert c.xshed("s") == []
        c.xshed_set("s", "batch", True)
        assert c.xshed("s") == ["batch"]
        with pytest.raises(ShedError):
            c.xadd("s", "YQ==", lane="batch")
        # other lanes keep flowing while batch sheds
        c.xadd("s", "Yg==", lane="interactive")
        c.xadd("s", "Yw==", lane="default")
        assert c.xlen("s") == 2
        c.xshed_set("s", "batch", False)
        assert c.xshed("s") == []
        c.xadd("s", "YQ==", lane="batch")
        assert c.xlen("s", "batch") == 1

    def test_xclaim_reclaims_interactive_before_batch(self, broker):
        """The lane/lease interplay at the broker layer: a dead
        consumer's pending entries re-deliver in lane-priority order, not
        arrival order."""
        c = broker.client()
        c.xadd("s", "YjA=", lane="batch")       # arrives FIRST
        c.xadd("s", "YjE=", lane="batch")
        c.xadd("s", "aTA=", lane="interactive")
        c.xadd("s", "aTE=", lane="interactive")
        assert len(c.xreadgroup("g", "dead", "s", 10, lanes=LANES)) == 4
        got = c.xclaim("s", "g", "live", 0, 10, lanes=LANES)
        assert [lane for _, lane, _ in got] == \
            ["interactive", "interactive", "batch", "batch"]
        # FIFO preserved within each lane
        assert [p for _, _, p in got] == ["aTA=", "aTE=", "YjA=", "YjE="]


# ------------------------------------------------------ client fast-fail

class TestClientShedFastFail:
    def test_enqueue_validation(self, broker):
        in_q = InputQueue(port=broker.port)
        try:
            with pytest.raises(ValueError):
                in_q.enqueue("v1", priority="urgent",
                             x=np.zeros(3, np.float32))
            for bad in (0, -5.0):
                with pytest.raises(ValueError):
                    in_q.enqueue("v2", deadline_ms=bad,
                                 x=np.zeros(3, np.float32))
            with pytest.raises(ValueError):
                in_q.enqueue("v3")              # no tensors at all
        finally:
            in_q.close()

    def test_shed_lane_raises_and_counts(self, broker):
        c = broker.client()
        c.xshed_set(STREAM, "batch", True)
        in_q = InputQueue(port=broker.port)
        label = f"stream={STREAM},priority=batch"
        shed0 = _counter("zoo_serving_shed_total", label)
        try:
            with pytest.raises(ShedError):
                in_q.enqueue("s1", priority="batch",
                             x=np.zeros(3, np.float32))
            # fast-fail is typed AND observable: the ledger counted it
            assert _counter("zoo_serving_shed_total", label) == shed0 + 1
            # interactive traffic keeps flowing through the same client
            in_q.enqueue("s2", priority="interactive",
                         x=np.zeros(3, np.float32))
            assert c.xlen(STREAM, "interactive") == 1
            with pytest.raises(ShedError):
                in_q.enqueue_batch(
                    [(f"sb{i}", {"x": np.zeros(3, np.float32)})
                     for i in range(2)], priority="batch")
            assert _counter("zoo_serving_shed_total", label) == shed0 + 2
        finally:
            in_q.close()


# ------------------------------------------------- engine lane scheduling

class _Track:
    """Doubler that records the distinct row markers of every batch it
    sees — the dispatch-order oracle for scheduling tests."""

    def __init__(self, sleep_s=0.0, first_sleep_s=0.0):
        self.sleep_s = sleep_s
        self.first_sleep_s = first_sleep_s
        self.calls = []

    def predict(self, x):
        x = np.asarray(x)
        first = self.first_sleep_s if not self.calls else 0.0
        self.calls.append(sorted(set(float(v) for v in x[:, 0])))
        if first or self.sleep_s:
            time.sleep(first or self.sleep_s)
        return x * 2.0


def _rec(marker):
    return {"x": np.full(3, float(marker), np.float32)}


def test_parse_lane_map():
    d = {lane: 0.0 for lane in schema.PRIORITIES}
    assert _parse_lane_map("", d) == d
    assert _parse_lane_map("250", d) == {k: 250.0 for k in d}
    out = _parse_lane_map("interactive=50, batch=4000", d)
    assert out["interactive"] == 50.0 and out["batch"] == 4000.0
    assert out["default"] == 0.0


def test_weighted_deficit_lane_order():
    with Broker.launch(backend="python") as b:
        eng = ClusterServing(_Track(), b.port, batch_size=4,
                             max_batch_size=4, warmup=False)
        # all credits zero: ties resolve to static priority order
        assert eng._lane_order() == LANES
        # a lane that consumed far more than its weighted share drops to
        # the back of the read order until the others catch up
        eng._lane_credit["interactive"] += 100.0
        assert eng._lane_order().split(",")[-1] == "interactive"
        eng._lane_credit["default"] += 1000.0
        order = eng._lane_order().split(",")
        assert order[0] == "batch" and order[-1] == "default"


def test_starvation_protection_batch_drains_under_interactive_load():
    """Weighted-deficit scheduling: with a deep interactive backlog AND
    queued batch work, the batch lane is served within the first few
    dispatches instead of waiting for the interactive queue to drain
    (strict-priority starvation), and every record still answers."""
    n_int, n_batch = 24, 4
    model = _Track(sleep_s=0.02)
    with Broker.launch(backend="python") as b:
        in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
        uris = list(in_q.enqueue_batch(
            (f"si{i}", _rec(1 + i)) for i in range(n_int)))
        uris += in_q.enqueue_batch(
            ((f"sb{i}", _rec(100 + i)) for i in range(n_batch)),
            priority="batch")
        with ClusterServing(model, b.port, batch_size=n_batch,
                            max_batch_size=n_batch, pipeline_window=1,
                            warmup=False) as eng:
            res = out_q.query_many(uris, timeout=30.0)
            assert eng.metrics()["records_expired"] == 0
        assert all(v is not None for v in res.values())
        batch_markers = {float(100 + i) for i in range(n_batch)}
        hit = [i for i, call in enumerate(model.calls)
               if batch_markers & set(call)]
        # credits: dispatch 0 drains 4 interactive (ratio 1 at weight 4),
        # so the batch lane (ratio 0) leads the very next read — well
        # before the 6 remaining interactive dispatches
        assert hit and hit[0] <= 2, \
            f"batch lane starved: served at dispatches {hit} " \
            f"of {len(model.calls)}"


def test_max_wait_dispatches_partial_bucket(monkeypatch):
    """A partial assembly bucket must dispatch once the oldest record
    has waited out its lane's max-wait — NOT hold out for a full batch
    that may never arrive."""
    monkeypatch.setenv("ZOO_SERVING_MAX_WAIT_MS", "150")
    model = _Track()
    with Broker.launch(backend="python") as b:
        with ClusterServing(model, b.port, batch_size=8, max_batch_size=8,
                            block_ms=20, warmup=False):
            in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
            t0 = time.monotonic()
            uris = list(in_q.enqueue_batch(
                (f"mw{i}", _rec(1 + i)) for i in range(3)))
            res = out_q.query_many(uris, timeout=30.0)
            dt = time.monotonic() - t0
        assert all(v is not None for v in res.values())
        # one padded dispatch carrying all three records, released by the
        # max-wait trigger: after the wait window, before forever
        assert len(model.calls) == 1, model.calls
        assert set(model.calls[0]) >= {1.0, 2.0, 3.0}
        assert 0.10 <= dt < 5.0, f"dispatch at {dt:.3f}s"


def test_deadline_slack_preempts_max_wait(monkeypatch):
    """A record whose deadline lands inside the max-wait window
    dispatches on its deadline slack — max-wait must never hold a record
    past the moment its result would go stale."""
    monkeypatch.setenv("ZOO_SERVING_MAX_WAIT_MS", "5000")
    model = _Track()
    with Broker.launch(backend="python") as b:
        with ClusterServing(model, b.port, batch_size=8, max_batch_size=8,
                            block_ms=20, warmup=False) as eng:
            in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
            t0 = time.monotonic()
            uri = in_q.enqueue("ds0", deadline_ms=300.0, **_rec(7))
            res = out_q.query(uri, timeout=30.0)
            dt = time.monotonic() - t0
            assert res is not None          # served, NOT expired
            assert eng.metrics()["records_expired"] == 0
        # released near the 300ms deadline, nowhere near the 5s max-wait
        assert dt < 3.0, f"held {dt:.3f}s despite a 300ms deadline"


@pytest.mark.parametrize("backend", BACKENDS)
def test_deadline_expiry_accounting(backend):
    """An expired record terminates as an EXPLICIT typed result on both
    broker backends: the client's query raises DeadlineExpiredError, the
    per-lane expired counter ticks, the entry is acked (no redelivery
    loop), and it never counts as a record error."""
    b = Broker.launch(backend=backend)
    try:
        in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
        label = f"stream={STREAM},priority=interactive"
        exp0 = _counter("zoo_serving_expired_total", label)
        err0 = _counter("zoo_serving_record_errors_total",
                        f"stream={STREAM}")
        # enqueue BEFORE the engine exists so the deadline lapses in queue
        dead = in_q.enqueue("exp0", priority="interactive",
                            deadline_ms=30.0, **_rec(1))
        live = in_q.enqueue("ok0", **_rec(2))
        time.sleep(0.1)
        with ClusterServing(_Track(), b.port, batch_size=2,
                            max_batch_size=2, warmup=False) as eng:
            np.testing.assert_allclose(
                out_q.query(live, timeout=30.0), np.full(3, 4.0))
            with pytest.raises(schema.DeadlineExpiredError):
                out_q.query(dead, timeout=30.0)
            assert eng.metrics()["records_expired"] == 1
        assert _counter("zoo_serving_expired_total", label) == exp0 + 1
        # expired ≠ error: availability SLOs must not burn on deadlines
        assert _counter("zoo_serving_record_errors_total",
                        f"stream={STREAM}") == err0
        c = b.client()
        assert c.xpending(STREAM, GROUP) == 0   # acked, not orphaned
    finally:
        b.stop()


# ---------------------------------------------- admission control (engine)

class _FakeMonitor:
    """Stands in for the SLO monitor: `burning` answers a test-set flag
    so the admission tick's broker side effects test deterministically."""

    def __init__(self):
        self.burn = False

    def tick_if_stale(self):
        pass

    def burning(self, name):
        return self.burn

    def stop(self):
        pass


def test_admission_tick_flips_broker_shed_flag():
    fake = _FakeMonitor()
    slo.set_monitor(fake)
    try:
        with Broker.launch(backend="python") as b:
            eng = ClusterServing(_Track(), b.port, batch_size=4,
                                 max_batch_size=4, warmup=False)
            c = b.client()
            eng._admission_tick(c)
            assert not eng.admission_shedding and c.xshed(STREAM) == []
            # burn starts: the BATCH lane sheds at the broker...
            fake.burn = True
            eng._last_admission = 0.0
            eng._admission_tick(c)
            assert eng.admission_shedding
            assert c.xshed(STREAM) == [eng.ADMISSION_LANE] == ["batch"]
            with pytest.raises(ShedError):
                c.xadd(STREAM, "YQ==", lane="batch")
            # ...while interactive admission is untouched
            c.xadd(STREAM, "Yg==", lane="interactive")
            assert _counter("zoo_serving_admission_state",
                            f"stream={STREAM},priority=batch") == 1.0
            # burn ends: the flag clears and batch flows again
            fake.burn = False
            eng._last_admission = 0.0
            eng._admission_tick(c)
            assert not eng.admission_shedding and c.xshed(STREAM) == []
            c.xadd(STREAM, "YQ==", lane="batch")
            assert _counter("zoo_serving_admission_state",
                            f"stream={STREAM},priority=batch") == 0.0
            # lane depth gauges refreshed from the broker on each tick
            assert _counter("zoo_serving_lane_depth",
                            f"stream={STREAM},priority=interactive") == 1.0
    finally:
        slo.set_monitor(None)


# --------------------------------------------------- lane/lease interplay

def test_lease_reclaim_serves_interactive_before_batch():
    """End-to-end lane/lease interplay: replica A takes a mixed
    interactive+batch delivery and stalls past its lease; replica B's
    reclaim sweep re-delivers lane-ordered, so A's interactive records
    are SERVED (not merely claimed) before its batch records."""
    n = 4                                       # per lane
    int_markers = {float(1 + i) for i in range(n)}
    batch_markers = {float(100 + i) for i in range(n)}
    with Broker.launch(backend="python") as b:
        in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
        # batch-lane records arrive FIRST: arrival order must not win
        uris = list(in_q.enqueue_batch(
            ((f"lb{i}", _rec(100 + i)) for i in range(n)),
            priority="batch"))
        uris += in_q.enqueue_batch(
            ((f"li{i}", _rec(1 + i)) for i in range(n)),
            priority="interactive")
        eng_a = ClusterServing(_Track(first_sleep_s=1.5), b.port,
                               batch_size=2 * n, max_batch_size=2 * n,
                               consumer="repA", claim_min_idle_ms=300,
                               reclaim_interval_s=30.0, warmup=False)
        eng_a.start()
        try:
            c = b.client()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if c.xpending_detail(STREAM, GROUP).get("repA") == 2 * n:
                    break
                time.sleep(0.02)
            assert c.xpending_detail(STREAM, GROUP) == {"repA": 2 * n}
            model_b = _Track()
            eng_b = ClusterServing(model_b, b.port, batch_size=2,
                                   max_batch_size=2, consumer="repB",
                                   claim_min_idle_ms=300,
                                   reclaim_interval_s=0.1, warmup=False)
            eng_b.start()
            try:
                res = out_q.query_many(uris, timeout=30.0)
                assert all(v is not None for v in res.values())
                # B's dispatch sequence: every interactive marker strictly
                # precedes every batch marker
                order = [set(call) for call in model_b.calls]
                last_int = max(i for i, s in enumerate(order)
                               if s & int_markers)
                first_batch = min(i for i, s in enumerate(order)
                                  if s & batch_markers)
                assert last_int < first_batch, \
                    f"batch served before interactive drained: {order}"
            finally:
                eng_b.stop()
        finally:
            eng_a.stop()


@pytest.mark.slow
def test_sigkill_reclaim_lane_order_drill():
    """Acceptance (ISSUE 10): SIGKILL a replica holding a mixed
    interactive+batch in-flight window (kill@replica fault seam). The
    survivor's lease reclaim must ANSWER the victim's interactive
    records before its batch-lane records, with zero loss."""
    n = 4                                       # per lane
    env = {"ZOO_SERVING_LEASE_MS": "300", "ZOO_SERVING_RECLAIM_S": "0.25",
           "ZOO_FLEET_HEARTBEAT_S": "0.25", "ZOO_FLEET_STALE_S": "1.0"}
    with resilience.fault_drill("kill@replica:1", cpu_fallback=False), \
            Broker.launch(backend="python") as broker:
        in_q = InputQueue(port=broker.port)
        int_uris = list(in_q.enqueue_batch(
            ((f"ki{i}", _rec(1 + i)) for i in range(n)),
            priority="interactive"))
        batch_uris = list(in_q.enqueue_batch(
            ((f"kb{i}", _rec(100 + i)) for i in range(n)),
            priority="batch"))
        victim = resilience.ServingReplicaProc(
            broker.port, batch_size=2 * n, predict_sleep_ms=60_000.0,
            env_extra=env)
        box = {}
        try:
            c = broker.client()
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline and \
                    c.xpending(STREAM, GROUP) < 2 * n:
                time.sleep(0.05)
            assert c.xpending(STREAM, GROUP) == 2 * n
            assert resilience.maybe_kill_replica(victim)
            assert not victim.alive
            # the survivor comes up AFTER the kill: everything it serves
            # arrived through the lane-ordered lease reclaim. Spawned off
            # a thread — its constructor blocks on subprocess imports,
            # and the poll loop must watch the drain LIVE to time the
            # per-lane result arrivals
            spawn = threading.Thread(target=lambda: box.update(
                proc=resilience.ServingReplicaProc(
                    broker.port, batch_size=2, predict_sleep_ms=400.0,
                    env_extra=env)))
            spawn.start()
            arrived = {}
            all_uris = int_uris + batch_uris
            deadline = time.monotonic() + 90.0
            while len(arrived) < 2 * n and time.monotonic() < deadline:
                vals = c.pipeline(("HGET", "result", u) for u in all_uris)
                now = time.monotonic()
                for u, v in zip(all_uris, vals):
                    if v is not None and u not in arrived:
                        arrived[u] = now
                time.sleep(0.005)
            spawn.join(timeout=60.0)
            missing = [u for u in all_uris if u not in arrived]
            assert not missing, f"{len(missing)} records lost after kill"
            # the engine pipelines dispatches, so mid-sequence flushes
            # can tie — but the FIRST record served after the kill must
            # be interactive and the LAST must be batch (the strict
            # per-dispatch order is asserted by the in-process twin,
            # test_lease_reclaim_serves_interactive_before_batch)
            first_int = min(arrived[u] for u in int_uris)
            first_batch = min(arrived[u] for u in batch_uris)
            assert first_int < first_batch, \
                "a batch-lane result was served before any interactive " \
                f"one ({first_int:.3f} vs {first_batch:.3f})"
            assert max(arrived[u] for u in int_uris) <= \
                max(arrived[u] for u in batch_uris)
        finally:
            if box.get("proc") is not None:
                box["proc"].stop()
            victim.stop()


# ---------------------------------------------------------- HTTP frontend

def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def _post_predict(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def test_frontend_lane_state_and_typed_answers():
    with Broker.launch(backend="python") as b:
        model = _Track(sleep_s=0.02)
        with ClusterServing(model, b.port, batch_size=4, max_batch_size=4,
                            warmup=False) as eng:
            fe = FrontEnd(b.port, engine=eng)
            fe.start()
            c = b.client()
            try:
                # healthy predict rides a lane end to end
                out = _post_predict(fe.port, {
                    "uri": "fe0", "priority": "interactive",
                    "deadline_ms": 30_000.0,
                    "inputs": {"x": schema.encode_tensor(
                        np.full(3, 2.0, np.float32))}})
                assert out["uri"] == "fe0"
                # /healthz and /slo expose the per-lane scheduling state
                hz = _get_json(f"http://127.0.0.1:{fe.port}/healthz")
                assert set(hz["lanes"]) == set(schema.PRIORITIES)
                assert hz["shed_lanes"] == []
                assert hz["admission"]["shedding"] is False
                rep = _get_json(f"http://127.0.0.1:{fe.port}/slo")
                assert set(rep["lanes"]) == set(schema.PRIORITIES)
                assert "admission" in rep
                # a shed lane answers 429 code=shed, instantly
                c.xshed_set(STREAM, "batch", True)
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post_predict(fe.port, {
                        "priority": "batch",
                        "inputs": {"x": schema.encode_tensor(
                            np.full(3, 1.0, np.float32))}})
                assert ei.value.code == 429
                assert json.loads(ei.value.read())["code"] == "shed"
                hz = _get_json(f"http://127.0.0.1:{fe.port}/healthz")
                assert hz["shed_lanes"] == ["batch"]
                c.xshed_set(STREAM, "batch", False)
                # an expired deadline answers 504 code=expired — occupy
                # the engine so a 1ms deadline deterministically lapses
                in_q = InputQueue(port=b.port)
                in_q.enqueue_batch(
                    (f"fill{i}", _rec(50 + i)) for i in range(8))
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post_predict(fe.port, {
                        "uri": "fe1", "deadline_ms": 1.0,
                        "inputs": {"x": schema.encode_tensor(
                            np.full(3, 3.0, np.float32))}})
                assert ei.value.code == 504
                body = json.loads(ei.value.read())
                assert body["code"] == "expired" and body["uri"] == "fe1"
                hz = _get_json(f"http://127.0.0.1:{fe.port}/healthz")
                assert hz["admission"]["records_expired"] >= 1
            finally:
                fe.stop()


# ------------------------------------------------- zero-silent-drops ledger

def test_every_enqueue_terminates_result_expired_or_shed():
    """The zero-silent-drops contract (ISSUE 10 acceptance): every
    enqueue attempt lands in exactly ONE terminal state — a result, a
    typed expired result, or a typed shed rejection — and each state is
    observable on a counter."""
    n_good, n_exp, n_shed = 4, 2, 2
    shed_label = f"stream={STREAM},priority=batch"
    exp_label = f"stream={STREAM},priority=default"
    with Broker.launch(backend="python") as b:
        in_q, out_q = InputQueue(port=b.port), OutputQueue(port=b.port)
        shed0 = _counter("zoo_serving_shed_total", shed_label)
        exp0 = _counter("zoo_serving_expired_total", exp_label)
        good = list(in_q.enqueue_batch(
            (f"zg{i}", _rec(1 + i)) for i in range(n_good)))
        expired = [in_q.enqueue(f"ze{i}", deadline_ms=25.0, **_rec(10 + i))
                   for i in range(n_exp)]
        time.sleep(0.1)                 # deadlines lapse in-queue
        c = b.client()
        c.xshed_set(STREAM, "batch", True)
        for i in range(n_shed):
            with pytest.raises(ShedError):
                in_q.enqueue(f"zs{i}", priority="batch", **_rec(20 + i))
        c.xshed_set(STREAM, "batch", False)
        with ClusterServing(_Track(), b.port, batch_size=4,
                            max_batch_size=4, warmup=False) as eng:
            res = out_q.query_many(good, timeout=30.0)
            assert all(v is not None for v in res.values())
            for u in expired:
                with pytest.raises(schema.DeadlineExpiredError):
                    out_q.query(u, timeout=30.0)
            m = eng.metrics()
            # accepted records partition exactly into served + expired
            assert m["records_out"] == n_good
            assert m["records_expired"] == n_exp
        assert _counter("zoo_serving_shed_total", shed_label) == \
            shed0 + n_shed
        assert _counter("zoo_serving_expired_total", exp_label) == \
            exp0 + n_exp
        assert c.xpending(STREAM, GROUP) == 0
        # attempts = terminal outcomes, nothing vanished
        assert n_good + n_exp + n_shed == \
            len(good) + len(expired) + n_shed
