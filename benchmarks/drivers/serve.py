"""Traffic kind ``serve``: records through ``InputQueue`` -> native broker
-> ``ClusterServing`` -> ``InferenceModel`` -> ``OutputQueue``.

One loop on one thread sends what is due and reads back what is ready, so
the load comes from one process with few threads. ``arrivals`` in the
traffic file is ``backlog`` (closed: keep ``outstanding`` records in
flight; the end-to-end metric is records per second read back) or
``poisson`` (open: a fixed rate from the file; the end-to-end metric is
the 95th percentile of answer time minus due time over ALL records due in
the window). The stream runs ``warm_seconds`` before the window opens, so
the window sees the ladder where sustained traffic leaves it.

After the window every record still in flight is waited for (a late answer
is late, not wrong), the engine and the model are freed, and the plain
reference answers a sample of the window's records drawn from the seed.
"""

import time

import numpy as np

from benchmarks.harness import arrivals, compare, tracing
from benchmarks.harness.window import now, percentile


class Prepared:
    pass


def setup(cell, seed: int) -> Prepared:
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )
    from analytics_zoo_tpu.serving.broker import build_native_broker

    t = cell.traffic
    model_lib = cell.module("models")
    ref_lib = cell.module("references")
    p = Prepared()
    p.pool = arrivals.record_pool(cell.config["vocab_size"],
                                  int(t["seq_len"]), int(t["pool"]), seed)
    model = InferenceModel()        # configures the compile cache first
    params = ref_lib.make_params(cell.config, seed)
    p.model = model.load_flax(model_lib.build_module(cell.config),
                              p.pool[:1], params={"params": params})
    del params
    if not build_native_broker():
        raise RuntimeError("the native broker did not build")
    p.broker = Broker.launch(backend="native")
    p.in_q = InputQueue(port=p.broker.port)
    p.out_q = OutputQueue(port=p.broker.port)
    e = t["engine"]
    p.engine = ClusterServing(
        p.model, p.broker.port, batch_size=e["batch_size"],
        min_batch_size=e["min_batch_size"],
        max_batch_size=e["max_batch_size"])
    p.engine.start()
    p.engine.wait_warm(timeout=1000.0)
    return p


class Stream:
    """The send-and-read loop and its books."""

    def __init__(self, cell, p: Prepared, seed: int, seconds: float):
        t = cell.traffic
        self.p, self.t = p, t
        self.key = t.get("input_name", "x")
        self.sent_at, self.due_at, self.answered_at = [], [], []
        self.answers = {}
        self.index = {}                     # uri -> record number
        self.enqueue_s = 0.0
        self.backlog = t["arrivals"] == "backlog"
        if not self.backlog:
            horizon = (float(t["warm_seconds"]) + seconds
                       + float(t.get("trace_seconds", 3.0)) + 1.0)
            self.schedule = arrivals.poisson_due_times(
                float(t["rate_per_s"]), horizon, seed)
        self.received = 0

    def _send(self, due_times):
        first = len(self.sent_at)
        pool = self.p.pool
        records = [(f"r{first + k}", {self.key: pool[(first + k) % len(pool)]})
                   for k in range(len(due_times))]
        t0 = now()
        with tracing.span("enqueue"):
            uris = self.p.in_q.enqueue_batch(records)
        t1 = now()
        self.enqueue_s += t1 - t0
        for k, (uri, due) in enumerate(zip(uris, due_times)):
            self.index[uri] = first + k
            self.due_at.append(t1 if due is None else due)
            self.sent_at.append(t1)
            self.answered_at.append(None)

    def _read(self):
        with tracing.span("read_results"):
            got = self.p.out_q.dequeue()
        t = now()
        for uri, value in got.items():
            i = self.index.get(uri)
            if i is None or self.answered_at[i] is not None:
                continue
            self.answered_at[i] = t
            self.answers[i] = np.asarray(value)
            self.received += 1

    def run_until(self, t_origin: float, t_end: float):
        """Send and read until ``t_end`` on the perf_counter clock."""
        chunk = int(self.t.get("send_chunk", 256))
        nxt = len(self.sent_at)
        while True:
            t = now()
            if t >= t_end:
                return
            if self.backlog:
                want = int(self.t["outstanding"]) - (len(self.sent_at)
                                                     - self.received)
                if want > 0:
                    self._send([None] * min(want, chunk))
            else:
                n = int(np.searchsorted(self.schedule, t - t_origin,
                                        side="right"))
                n = min(n, nxt + chunk)
                if n > nxt:
                    self._send(list(t_origin + self.schedule[nxt:n]))
                    nxt = n
            self._read()
            time.sleep(float(self.t.get("poll_s", 0.002)))

    def drain(self, timeout_s: float):
        deadline = now() + timeout_s
        while self.received < len(self.sent_at) and now() < deadline:
            self._read()
            time.sleep(0.005)


def _engine_books(p: Prepared) -> dict:
    from analytics_zoo_tpu.common import telemetry
    m = p.engine.metrics()
    stages = {k: (v["count"], v["total_s"]) for k, v in m.items()
              if isinstance(v, dict) and "total_s" in v}
    return {"records_out": m["records_out"], "stages": stages,
            "rung": p.engine.batch_size,
            "rung_changes": (m.get("batch_size") or {}).get("count", 0),
            "fallbacks": p.model._exec_cache.fallbacks,
            "telemetry": telemetry.snapshot()}


def window(cell, p: Prepared, seed: int, seconds: float, trace: bool,
           listener) -> dict:
    t = cell.traffic
    s = Stream(cell, p, seed, seconds)
    warm = float(t["warm_seconds"])
    t_origin = now()
    s.run_until(t_origin, t_origin + warm)
    t0 = t_origin + warm
    p.setup_done_at = now()
    listener.mark()
    before = _engine_books(p)
    first_in_window = len(s.sent_at)
    received_before = s.received

    t_end = t0 + seconds
    s.run_until(t_origin, t_end)
    closed = now()
    read_in_window = s.received - received_before
    after = _engine_books(p)
    compiles = listener.since_mark()
    # a traced run lets the same stream run on under the profiler: the
    # rate and the tail are never taken with the profiler on
    profiler, traced_units = None, 0
    if trace:
        profiler = tracing.ProfilerWindow(cell.chips)
        out0 = p.engine.metrics()["records_out"]
        profiler.start()
        s.run_until(t_origin, now() + float(t.get("trace_seconds", 3.0)))
        profiler.stop()
        traced_units = p.engine.metrics()["records_out"] - out0
    s.drain(float(t.get("drain_timeout_s", 60.0)))

    # due in the window, not in the traced stretch after it
    due = [d for d in s.due_at[first_in_window:] if d < t_end]
    idx = range(first_in_window, first_in_window + len(due))
    answered = [s.answered_at[i] for i in idx]
    lat = arrivals.latencies_ms(due, answered)
    late = [(s.sent_at[i] - s.due_at[i]) * 1e3 for i in idx]
    return {
        "stream": s, "records": list(idx), "elapsed_s": closed - t0,
        "attempted": len(due),
        "failed": sum(1 for a in answered if a is None),
        "rate": read_in_window / (closed - t0),
        "latencies_ms": lat, "generator_late_ms": late,
        "compiles": compiles, "books": {"start": before, "end": after},
        "telemetry": {"start": before["telemetry"],
                      "end": after["telemetry"]},
        "enqueue_us": 1e6 * s.enqueue_s / max(1, len(s.sent_at)),
        "profiler": profiler,
        "traced_units": traced_units,
    }


def shutdown(p: Prepared):
    """Stop the engine and the broker and drop the model's device state."""
    p.engine.stop()
    p.in_q.close()
    p.broker.stop()
    p.engine = p.model = None


def sample_records(w: dict, seed: int, k: int) -> list:
    """``k`` of the window's answered records, drawn from the seed."""
    s = w["stream"]
    done = [i for i in w["records"] if s.answered_at[i] is not None]
    rng = np.random.default_rng(seed + 1)
    return sorted(rng.choice(done, size=min(k, len(done)),
                             replace=False).tolist())


def reference_logits(cell, seed: int, ids, quant=None):
    """The plain reference's answers to ``ids``, in blocks of rows."""
    import jax
    ref_lib = cell.module("references")
    params = ref_lib.make_params(cell.config, seed)
    block = int(cell.traffic["reference_block_rows"])
    fwd = jax.jit(lambda prm, x: ref_lib.forward(prm, x, cell.config,
                                                 quant=quant))
    out = [np.asarray(fwd(params, ids[k:k + block]))
           for k in range(0, len(ids), block)]
    return np.concatenate(out)


def gaps(got, want) -> dict:
    """Root-mean-square and largest gap of the served answers from the
    reference's, against the reference's root-mean-square answer."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = float(np.sqrt(np.mean(want * want)))
    d = got - want
    return {"answer_rms": float(np.sqrt(np.mean(d * d))) / scale,
            "answer_max": float(np.max(np.abs(d))) / scale}


def served_and_reference(cell, seed: int, w: dict) -> tuple:
    """(records' ids, served answers, the reference's answers) for the
    sample of the window's records."""
    s = w["stream"]
    picked = sample_records(w, seed, int(cell.traffic["check_records"]))
    ids = s.p.pool[[i % len(s.p.pool) for i in picked]]
    got = np.stack([s.answers[i].reshape(-1) for i in picked])
    return ids, got, reference_logits(cell, seed, ids)


def verify(cell, seed: int, w: dict) -> tuple:
    ids, got, want = served_and_reference(cell, seed, w)
    checks = compare.Checks(cell.limits)
    g = dict(gaps(got, want), unanswered=float(w["failed"]))
    for name in cell.limits:
        checks.add(name, g[name])
    return checks, [f"compared {len(ids)} of {w['attempted']} records "
                    f"with the reference"]


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        listener) -> dict:
    from benchmarks.harness import device
    t_a = now()
    p = setup(cell, seed)
    t_b = now()
    w = window(cell, p, seed, seconds, trace, listener)
    setup_s = p.setup_done_at - t_start
    peak = device.memory_peak_bytes(cell.chips)
    shutdown(p)
    t_c = now()
    checks, notes = verify(cell, seed, w)
    notes.append(f"phases s: imports {t_a - t_start:.1f}, model, broker and "
                 f"ladder {t_b - t_a:.1f}, warm stream "
                 f"{p.setup_done_at - t_b:.1f}, reference and comparison "
                 f"{now() - t_c:.1f}")
    t = cell.traffic
    finite = [x for x in w["latencies_ms"] if x != float("inf")]
    notes.append(
        f"window: {w['attempted']} records due, {w['failed']} unanswered, "
        f"{w['rate']:.3f} records/s read back; latency ms over "
        f"{len(w['latencies_ms'])} records: p50 "
        f"{percentile(w['latencies_ms'], 50):.3f} p95 "
        f"{percentile(w['latencies_ms'], 95):.3f} max "
        f"{max(finite) if finite else float('inf'):.3f}; lowerings/compiles "
        f"inside {w['compiles']['count']}; rung at end "
        f"{w['books']['end']['rung']}")
    value = (w["rate"] if t["end_metric"] == "rate"
             else percentile(w["latencies_ms"], float(t["percentile"])))
    return {"attempted": w["attempted"], "failed": w["failed"],
            "end_to_end": {t["end_metric_name"]: value, "setup_s": setup_s},
            "checks": checks, "notes": notes, "memory_peak_bytes": peak,
            "evidence": w, "mode": "serve"}


def control_readings(cell, seed: int, seconds: float, with_control: bool,
                     listener) -> dict:
    """For ``benchmarks/control.py``: a short window at the cell's own
    load, the served answers' gaps from the reference, and
    (``with_control``) the gaps of the reference computed in the control's
    precision on the same records."""
    ref_lib = cell.module("references")
    p = setup(cell, seed)
    w = window(cell, p, seed, seconds, False, listener)
    shutdown(p)
    ids, got, want = served_and_reference(cell, seed, w)
    out = {"program": dict(gaps(got, want), unanswered=w["failed"],
                           rate=w["rate"],
                           p95_ms=percentile(w["latencies_ms"], 95.0))}
    if with_control:
        for name in cell.traffic["control"].split(","):
            out[f"control_{name}"] = gaps(reference_logits(
                cell, seed, ids, quant=getattr(ref_lib, name)), want)
        swapped = got.copy()
        swapped[[0, 1]] = swapped[[1, 0]]
        out["fault_swapped_answer"] = gaps(swapped, want)
    return out
