"""Metric history & cost attribution (ISSUE 17): the bounded in-process
time-series store (windowed rate/quantile answers from ring samples),
exemplar-linked traces on the serving hot path, fleet history merge
through the snapshot algebra, per-request cost accounting, and the
``/metrics/history`` + ``/query`` HTTP surface."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common import telemetry, timeseries
from analytics_zoo_tpu.common.telemetry import MetricsRegistry


def _get_json(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


# ------------------------------------------------------ window algebra


def test_counter_rate_and_delta_from_window_edges():
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
    c = telemetry.get_registry().counter("zoo_ts_unit_total", "d")
    c.inc(10)
    store.tick(now=0.0)
    c.inc(30)
    store.tick(now=10.0)
    out = store.query("zoo_ts_unit_total", window=10.0, now=10.0)
    assert out["agg"] == "rate"          # counter default
    (pt,) = out["points"]
    assert pt["value"] == pytest.approx(3.0)     # 30 events / 10 s
    assert pt["covered_s"] == pytest.approx(10.0)
    d = store.query("zoo_ts_unit_total", window=10.0, agg="delta",
                    now=10.0)["points"][0]["value"]
    assert d == pytest.approx(30.0)
    # a narrower window excludes the older edge: base falls back to the
    # point at/before the window start, not the series origin
    c.inc(5)
    store.tick(now=20.0)
    r = store.query("zoo_ts_unit_total", window=10.0, agg="rate",
                    now=20.0)["points"][0]["value"]
    assert r == pytest.approx(0.5)               # 5 events / 10 s


def test_gauge_window_aggregates():
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
    g = telemetry.get_registry().gauge("zoo_ts_unit_depth", "d")
    for t, v in ((0.0, 2.0), (5.0, 8.0), (10.0, 4.0)):
        g.set(v)
        store.tick(now=t)
    q = lambda agg: store.query("zoo_ts_unit_depth", window=10.0,
                                agg=agg, now=10.0)["points"][0]["value"]
    assert q("last") == 4.0
    assert q("max") == 8.0
    assert q("min") == 2.0
    assert q("avg") == pytest.approx((2.0 + 8.0 + 4.0) / 3)
    with pytest.raises(ValueError):
        store.query("zoo_ts_unit_depth", window=10.0, agg="p99", now=10.0)


def test_windowed_p99_matches_offline_recompute_within_bucket():
    """Acceptance (ISSUE 17): ``p99(window)`` comes from bucket-count
    deltas at the window edges and must agree with an offline
    recomputation from the raw tick samples to within one bucket
    bound — including forgetting out-of-window traffic the cumulative
    reservoir would remember forever."""
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
    h = telemetry.get_registry().histogram(
        "zoo_ts_unit_seconds", "d",
        buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0))
    rng = np.random.RandomState(7)
    # era 1 (ages out of the window): fast traffic
    for v in rng.uniform(0.001, 0.02, size=200):
        h.observe(float(v))
    store.tick(now=0.0)
    # era 2 (in-window): slow traffic
    in_window = [float(v) for v in rng.uniform(0.2, 3.0, size=300)]
    for v in in_window:
        h.observe(v)
    store.tick(now=60.0)

    val = store.query("zoo_ts_unit_seconds", window=60.0, agg="p99",
                      now=60.0)["points"][0]["value"]

    # offline recompute from the raw ring samples: subtract the bucket
    # vectors at the window edges, walk the cumulative counts to the
    # bucket containing the 99th percentile
    ser = store._series[("zoo_ts_unit_seconds", "")]
    pts = list(ser.points)
    base, last = pts[0], pts[-1]
    d_counts = [a - b for a, b in zip(last[3], base[3])]
    total = last[1] - base[1]
    assert total == 300
    le = list(ser.le) + [float("inf")]
    acc, lo, hi = 0, 0.0, le[-1]
    for i, c in enumerate(d_counts):
        acc += c
        if acc >= 0.99 * total:
            lo = le[i - 1] if i else 0.0
            hi = le[i]
            break
    assert lo <= val <= hi, (val, lo, hi)
    # and the true empirical p99 of what was observed in-window lands in
    # the same bucket bound
    true_p99 = float(np.percentile(in_window, 99))
    assert lo <= true_p99 <= hi
    # the windowed answer is NOT polluted by era-1 traffic: a cumulative
    # quantile over all 500 samples would sit far below the window's
    assert val >= 0.5


def test_ring_capacity_bounds_points_and_covered_s_reports_partial():
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=4)
    c = telemetry.get_registry().counter("zoo_ts_unit_total", "d")
    for t in range(10):
        c.inc(1)
        store.tick(now=float(t * 5))
    assert store.points_held() <= 4 * store.series_held()
    # a 1h window over a ring that only holds 15s of history answers
    # with covered_s == what the data supports, not the asked window
    pt = store.query("zoo_ts_unit_total", window=3600.0, agg="delta",
                     now=45.0)["points"][0]
    assert pt["covered_s"] == pytest.approx(15.0)
    assert pt["value"] == pytest.approx(3.0)     # 3 increments survive


def test_series_born_after_start_reads_implicit_zero_base():
    """A counter/histogram registered AFTER the store began ticking
    genuinely started from zero — the window delta must be the full
    total, not zero (the one-point ring would otherwise make base ==
    last). This is what keeps SLO burn alive for late-registered
    series."""
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
    store.tick(now=0.0)                  # store is live, series is not
    c = telemetry.get_registry().counter("zoo_ts_unit_total", "d")
    c.inc(7)
    store.tick(now=5.0)                  # first (and only) point
    d, covered = store.window_scalar_delta("zoo_ts_unit_total",
                                           window=60.0, now=5.0)
    assert d == pytest.approx(7.0)
    assert covered > 0


# ------------------------------------------------------- fleet history


def test_fleet_window_merge_property_rates_add():
    """Property (ISSUE 17 satellite): merging two replicas' windowed
    deltas through ``merge_snapshot`` gives exactly the delta of the
    merged counters — so fleet rate == sum of per-replica rates, and
    histogram bucket deltas add elementwise."""
    rng = np.random.RandomState(3)
    deltas, windows, totals = [], [], []
    for _ in range(2):                   # two simulated replicas
        telemetry.reset_for_tests()
        store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
        reg = telemetry.get_registry()
        c = reg.counter("zoo_ts_prop_total", "d", ("stream",)
                        ).labels("s1")
        h = reg.histogram("zoo_ts_prop_seconds", "d",
                          buckets=(0.1, 1.0))
        base_inc = int(rng.randint(0, 50))
        c.inc(base_inc)
        for v in rng.uniform(0.01, 2.0, size=int(rng.randint(1, 40))):
            h.observe(float(v))
        store.tick(now=0.0)
        t0 = c.value
        inc = int(rng.randint(1, 100))
        c.inc(inc)
        obs = [float(v) for v in rng.uniform(0.01, 2.0,
                                             size=int(rng.randint(1, 40)))]
        for v in obs:
            h.observe(v)
        store.tick(now=60.0)
        deltas.append((inc, len(obs)))
        totals.append((t0, c.value))
        windows.append(store.windows_delta((60.0,), now=60.0)["60s"])

    merged = MetricsRegistry.merge_snapshot(windows[0], windows[1])
    want_delta = deltas[0][0] + deltas[1][0]
    assert merged["zoo_ts_prop_total"]["stream=s1"] == \
        pytest.approx(want_delta)
    # delta of the merged raw counters over the same edges — identical
    fleet_t0 = sum(t[0] for t in totals)
    fleet_t1 = sum(t[1] for t in totals)
    assert fleet_t1 - fleet_t0 == pytest.approx(want_delta)
    # merged windowed rate == sum of per-replica windowed rates
    assert merged["zoo_ts_prop_total"]["stream=s1"] / 60.0 == \
        pytest.approx(sum(w["zoo_ts_prop_total"]["stream=s1"] / 60.0
                          for w in windows))
    mh = merged["zoo_ts_prop_seconds"]
    assert mh["count"] == deltas[0][1] + deltas[1][1]
    assert mh["bucket_counts"] == [
        a + b for a, b in zip(windows[0]["zoo_ts_prop_seconds"]
                              ["bucket_counts"],
                              windows[1]["zoo_ts_prop_seconds"]
                              ["bucket_counts"])]


def test_fleet_history_dead_replica_degrades_to_partial():
    """A registered-but-dead peer lands in ``failed`` and the fleet
    history answer degrades to partial — local retained windows are
    served untouched, never poisoned by the failed scrape."""
    import time

    from analytics_zoo_tpu.common import fleet
    from analytics_zoo_tpu.serving.broker import Broker
    from analytics_zoo_tpu.serving.frontend import scrape_fleet_history

    with Broker.launch(backend="python") as broker:
        reg = fleet.ReplicaRegistry("127.0.0.1", broker.port)
        now = time.time()
        reg.publish(fleet.ReplicaInfo("serving:9:dead", port=1,
                                      started_at=now, last_heartbeat=now))
        c = telemetry.get_registry().counter("zoo_ts_local_total")
        store = timeseries.get_store()
        c.inc(0)                          # series exists at the base tick
        store.tick()
        c.inc(4)
        store.tick()
        merged, meta = scrape_fleet_history("127.0.0.1", broker.port,
                                            windows=(60.0,),
                                            timeout_s=0.5)
        assert meta["failed"] == ["serving:9:dead"]
        assert merged["60s"]["zoo_ts_local_total"] == pytest.approx(4.0)
        snap = telemetry.snapshot()
        assert snap["zoo_fleet_scrape_errors_total"] == \
            {"replica=serving:9:dead": 1.0}
        # local rings survived the failed scrape intact
        again, _ = scrape_fleet_history("127.0.0.1", broker.port,
                                        windows=(60.0,), timeout_s=0.5)
        assert again["60s"]["zoo_ts_local_total"] >= 4.0


# --------------------------------------------------- exemplars & traces


def test_histogram_exemplars_bounded_and_in_prometheus_text():
    reg = telemetry.get_registry()
    h = reg.histogram("zoo_ts_unit_seconds", "d", buckets=(0.1, 1.0))
    h.observe(0.05, exemplar="trace-a")
    h.observe(0.07, exemplar="trace-b")   # same bucket: latest wins
    h.observe(0.5, exemplar="trace-c")
    h.observe(2.0)                        # no exemplar: slot stays empty
    exs = h.labels()._exemplar_state()
    assert len(exs) == 2                  # bounded: one slot per bucket
    assert exs[0][0] == "trace-b"
    assert exs[1][0] == "trace-c"
    text = telemetry.prometheus_text()
    assert '# {trace_id="trace-b"} 0.07' in text
    assert '# {trace_id="trace-c"} 0.5' in text


def test_trace_eviction_counter_counts_lru_drops():
    tr = telemetry.Tracer(capacity=2)
    for i in range(5):
        tr.record(f"uri-{i}", "stage", 0.0, 1.0)
    snap = telemetry.snapshot()
    assert snap["zoo_trace_evictions_total"] == 3.0


def test_query_exemplar_rides_trace_sampling_decision():
    """Exemplars attach only when the record's spans were actually
    recorded, so every exposed trace id resolves on ``/trace``."""
    store = timeseries.TimeSeriesStore(tick_s=5.0, max_points=64)
    h = telemetry.get_registry().histogram(
        "zoo_ts_unit_seconds", "d", buckets=(0.1, 1.0))
    store.tick(now=0.0)
    h.observe(0.5, exemplar="uri-sampled")
    h.observe(0.6)                        # unsampled record: no exemplar
    store.tick(now=5.0)
    out = store.query("zoo_ts_unit_seconds", window=60.0, agg="p99",
                      now=5.0)
    (pt,) = out["points"]
    assert pt["exemplar"]["trace_id"] == "uri-sampled"
    assert pt["exemplar"]["value"] == pytest.approx(0.5)


# --------------------------------------------- HTTP surface, end-to-end


def test_lane_depth_ring_shows_a_flood_ramp_and_recover(monkeypatch):
    """A batch-lane flood behind a live ``FrontEnd``, the store sampling
    on a fast tick: ``/metrics/history`` shows the lane's depth at zero
    before the flood, readable above zero while the flood still drains,
    at a batch or more at its peak, and back at zero once every record
    is answered."""
    import time

    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, FrontEnd, InputQueue, OutputQueue,
    )

    tick_s, batch, n = 0.05, 4, 96

    class SleepDuck:
        def predict(self, x):
            time.sleep(0.025)
            return np.asarray(x) * 2.0

    def batch_depths(base):
        hist = _get_json(base + "/metrics/history"
                                "?name=zoo_serving_lane_depth")
        return [p["value"] for s in hist["series"]
                if s["name"] == "zoo_serving_lane_depth"
                and s["labels"].get("priority") == "batch"
                for p in s["points"]]

    def wait_for(base, what, cond, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            depths = batch_depths(base)
            if cond(depths):
                return depths
            time.sleep(tick_s)
        raise AssertionError(f"{what}: {depths[-8:]}")

    # the lane gauges refresh on the engine's admission tick: at its
    # default second the whole flood drains between two refreshes
    monkeypatch.setenv("ZOO_SERVING_ADMISSION_S", str(tick_s))
    timeseries.set_store(timeseries.TimeSeriesStore(tick_s=tick_s))
    rng = np.random.default_rng(31)
    payloads = rng.standard_normal((n, 6)).astype(np.float32)
    with Broker.launch(backend="python") as broker:
        eng = ClusterServing(SleepDuck(), broker.port, batch_size=batch,
                             max_batch_size=batch, pipeline_window=2,
                             block_ms=10, warmup=False)
        fe = FrontEnd(broker.port, engine=eng)
        try:
            with eng.start():
                fe.start()
                base = f"http://127.0.0.1:{fe.port}"
                wait_for(base, "no zero point before the flood",
                         lambda d: d and d[-1] == 0)
                flood = InputQueue(port=broker.port).enqueue_batch(
                    ((f"hb{i}", {"x": payloads[i]}) for i in range(n)),
                    priority="batch")
                wait_for(base, "no ramp readable while the flood drains",
                         lambda d: d and max(d) > 0, timeout=10.0)
                res = OutputQueue(port=broker.port).query_many(
                    flood, timeout=90.0)
                assert all(v is not None for v in res.values())
                depths = wait_for(
                    base, "the lane's depth never came back to 0",
                    lambda d: d and d[-1] == 0)
        finally:
            fe.stop()
    assert min(depths) == 0 and max(depths) >= batch
    assert len(depths) >= 3


@pytest.mark.slow
def test_history_query_cost_and_healthz_decode_end_to_end():
    """Acceptance drill (ISSUE 17): encode + generate records flow
    through a live engine, then ``/query`` answers a windowed p99 whose
    point carries an exemplar resolvable via ``/trace``;
    ``/metrics/history`` serves the rings; the request-cost histograms
    hold both ``kind="encode"`` and ``kind="generate"`` settlements; and
    ``/healthz`` carries the ``decode`` occupancy block."""
    from analytics_zoo_tpu.models import Seq2Seq
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, FrontEnd, InputQueue, OutputQueue,
    )
    from analytics_zoo_tpu.inference import InferenceModel

    m = Seq2Seq(input_dim=3, output_dim=2, hidden_size=8, rnn_type="gru",
                encoder_seq_len=5, decoder_seq_len=4)
    im = InferenceModel().load_zoo(m)
    rng = np.random.RandomState(0)
    enc = rng.randn(5, 3).astype(np.float32)
    start = np.zeros(2, np.float32)

    b = Broker.launch(backend="python")
    eng = ClusterServing(im, b.port, batch_size=4, warmup=False)
    eng.start()
    fe = FrontEnd(b.port, engine=eng).start()
    try:
        in_q = InputQueue(port=b.port)
        out_q = OutputQueue(port=b.port)
        gen_uri = in_q.enqueue("ts_e2e_gen",
                               generate={"max_new_tokens": 8,
                                         "mode": "raw"},
                               x=enc, start=start)
        res = out_q.query(gen_uri, timeout=90.0)
        assert res is not None and res.shape == (8, 2)
        for i in range(4):
            uri = in_q.enqueue(f"ts_e2e_{i}", a_enc=enc,
                               b_dec=np.zeros((4, 2), np.float32))
            assert out_q.query(uri, timeout=60.0) is not None

        base = f"http://127.0.0.1:{fe.port}"
        q = _get_json(base + "/query?name=zoo_serving_latency_seconds"
                             "&window=60&agg=p99")
        assert q["agg"] == "p99" and q["points"], q
        vals = [p["value"] for p in q["points"] if p["value"] is not None]
        assert vals and all(v > 0 for v in vals)
        exs = [p["exemplar"] for p in q["points"] if "exemplar" in p]
        assert exs, q                     # >= 1 point carries an exemplar
        trace_id = exs[0]["trace_id"]
        tr = _get_json(base + f"/trace?uri={trace_id}")
        assert tr.get("traceEvents"), trace_id   # resolvable trace link

        # label filtering: any non-reserved param is an equality filter
        flt = _get_json(base + "/query?name=zoo_serving_latency_seconds"
                               "&window=60&priority=batch")
        assert all(p["labels"].get("priority") == "batch"
                   for p in flt["points"])

        hist = _get_json(base + "/metrics/history"
                                "?name=zoo_serving_lane_depth")
        assert any(s["name"] == "zoo_serving_lane_depth" and s["points"]
                   for s in hist["series"])
        wins = _get_json(base + "/metrics/history?format=windows"
                                "&windows=60")
        assert "zoo_serving_records_total" in wins["windows"]["60s"]

        # cost attribution settled for BOTH kinds
        snap = telemetry.snapshot()
        cost = snap["zoo_request_cost_device_seconds"]
        kinds = {telemetry._parse_label_key(k)[1][
            telemetry._parse_label_key(k)[0].index("kind")]: v
            for k, v in cost.items() if v["count"] > 0}
        assert "encode" in kinds and "generate" in kinds, cost
        assert all(v["sum"] >= 0 for v in cost.values())
        steps = snap["zoo_request_cost_decode_steps"]
        assert any(v["count"] > 0 and v["sum"] >= 8
                   for v in steps.values()), steps
        pages = snap["zoo_request_cost_kv_pages"]
        assert any(v["count"] > 0 and v["sum"] >= 1
                   for v in pages.values()), pages

        # /healthz decode occupancy block (an SLO shed in this tiny run
        # answers 503 but the body is still the full document)
        try:
            with urllib.request.urlopen(base + "/healthz") as r:
                hz = json.loads(r.read())
        except urllib.error.HTTPError as e:
            hz = json.loads(e.read())
        dec = hz.get("decode") or {}
        assert {"live_sequences", "preemptions", "pages_in_use",
                "pages_free"} <= set(dec)
        assert dec["live_sequences"] == 0         # everything retired
        assert dec["pages_in_use"] == 0

        # HTTP error contract
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/query", timeout=10)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                base + "/query?name=zoo_serving_latency_seconds"
                       "&agg=bogus", timeout=10)
        assert ei.value.code == 400
    finally:
        fe.stop()
        eng.stop()
        b.stop()
