"""ClusterServing engine — stream in → batch → TPU inference → result store.

TPU-native replacement for the reference's Flink job (SURVEY.md §3.4):
``FlinkRedisSource`` (XREADGROUP consumer-group batches,
FlinkRedisSource.scala:81) → ``FlinkInference.map`` (decode, batch predict
through InferenceModel, FlinkInference.scala:67-81) → ``FlinkRedisSink``
(HSET results). The Flink ``RichMapFunction`` parallelism becomes host
threads feeding ONE compiled executable: on TPU the model replica count of
the reference ("parallelism = model parallelism", ClusterServing.scala:54-67)
is the wrong knob — a single jitted forward at a fixed batch bucket keeps
the MXU saturated, so the engine pads each dequeued batch up to
``batch_size`` and masks the tail (same trick the reference applies per-core
via its batch slicing, tf_dataset.py:117).

Per-stage latency stats mirror serving ``Timer.scala:26``.

The serve loop is a produce → staged-dispatch → drain pipeline
(common/pipeline_io.py): dequeue/decode/preprocess of batch N+1 overlaps
batch N's device compute through a bounded in-flight window, and results
are only fetched when the window is full or the stream idles — a
synchronous loop leaves the accelerator idle during every broker
round-trip.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

# StageTimer moved to the shared pipeline layer; re-exported here because
# the engine is its historical home.
from analytics_zoo_tpu.common import compile_ahead, fleet, resilience, \
    slo, telemetry, timeseries
from analytics_zoo_tpu.common.pipeline_io import (  # noqa: F401
    Completed,
    DevicePipeline,
    StageTimer,
)
from analytics_zoo_tpu.inference import decode_scheduler, generation
from analytics_zoo_tpu.serving import schema
from analytics_zoo_tpu.serving.broker import Broker, BrokerClient
from analytics_zoo_tpu.serving.client import INPUT_STREAM, RESULT_HASH

logger = logging.getLogger(__name__)


def _parse_lane_map(raw: str, defaults: Dict[str, float]) -> Dict[str, float]:
    """Per-lane float knob: ``"40"`` applies to every lane,
    ``"interactive=5,batch=250"`` sets named lanes (unnamed lanes keep
    their default). Malformed parts raise — a silently-ignored scheduling
    knob is worse than a crash at construction."""
    out = dict(defaults)
    raw = (raw or "").strip()
    if not raw:
        return out
    if "=" not in raw:
        v = float(raw)
        return {k: v for k in out}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        out[k.strip()] = float(v)
    return out


def ndarray_chain(pipe):
    """Wrap a ChainedPreprocessing over ImageFeature dicts as a plain
    ndarray -> ndarray callable (the engine's ``image_preprocess``
    contract). One definition — the config-driven and preset-driven paths
    must not drift."""
    def run(arr):
        return pipe.transform({"image": np.asarray(arr, np.float32)}
                              )["image"]
    return run


def image_pipeline(model_name: str, source: str = "imagenet"):
    """ndarray -> ndarray preprocessing chain from the model zoo's
    per-model presets (ref ImagenetConfig preprocessors feeding
    PreProcessing.scala) — pass as ``ClusterServing(image_preprocess=)``.
    ``source="torchvision"`` selects the normalization trained into
    torchvision checkpoints (use with ``ImageClassifier(pretrained=)``)."""
    from analytics_zoo_tpu.models.image.imageclassification. \
        image_classifier import preprocessor
    return ndarray_chain(preprocessor(model_name, source=source))


class _GenBatch:
    """One assembled autoregressive-generate batch riding the dispatch
    pipeline: the encoder prefill tensor, the decoder start sign, and the
    request's decode parameters (schema.validate_generate wire form).
    ``_dispatch`` routes it onto the model's decode loop instead of the
    one-shot predict; the host-side result rides the pipeline window the
    way the CPU-failover result does."""

    __slots__ = ("enc", "start", "params", "trace_uris")

    def __init__(self, enc, start, params, trace_uris=()):
        self.enc = enc
        self.start = start
        self.params = dict(params)
        self.trace_uris = tuple(trace_uris)


class ClusterServing:
    """The serving job (ref ClusterServing.scala:31).

    ``model``: an InferenceModel (already loaded). ``input_cols``: the order
    in which record tensors feed the model's inputs (single-input models
    take the record's only tensor).

    ``image_preprocess``: ndarray -> ndarray chain applied to records that
    arrive as raw encoded images (``InputQueue.enqueue(uri, image=bytes)``)
    after the engine decodes them — the reference's server-side
    decode-and-preprocess flow (PreProcessing.scala:36,67-90). Build one
    from a preset with ``image_pipeline("resnet-50", source=...)`` or wire
    it from config.yaml's ``preprocessing:`` section.

    ``pipeline_window``: how many dispatched batches may be in flight on
    the device while the loop dequeues/preprocesses the next ones (0 =
    fully synchronous dispatch — the reference tests/test_pipeline_io.py
    compares the windowed results with, bit for bit).

    ``max_batch_size``: cap for adaptive batch growth. Under sustained
    backlog (every dequeue returns a full batch) the engine steps its
    batch bucket up the ladder to this cap — fewer, bigger dispatches win
    when the per-dispatch cost dominates. ``None`` defaults to 4×
    ``batch_size``; set it equal to ``batch_size`` to pin the bucket.

    ``min_batch_size``: the bottom rung the bucket may shrink back to
    after sustained idle (defaults to ``batch_size``: no shrinking).

    ``warmup``: AOT-compile the whole bucket ladder on a background
    thread at ``start()``, so a backlog-driven bucket change is a
    stall-free swap to an already-compiled rung instead of an in-band XLA
    compile on the serve thread. On by default for models that support it (InferenceModel);
    ``ZOO_WARMUP_BUCKETS=0`` disables it process-wide, any other integer
    caps how many rungs (smallest first) are warmed.

    Multi-replica fan-out: ``consumer`` defaults to this replica's fleet
    id, so N engines sharing one ``group`` split the stream with
    at-least-once delivery — each delivered entry carries a per-consumer
    lease (``claim_min_idle_ms``, env ``ZOO_SERVING_LEASE_MS``), and a
    periodic reclaim sweep (env ``ZOO_SERVING_RECLAIM_S``) claims peers'
    expired leases so a crashed replica's entries are re-served with zero
    loss (docs/observability.md "Multi-replica deployment").

    SLO-aware scheduling: records carry a priority lane
    (``schema.PRIORITIES``) and an optional ``deadline_ms``. Reads are
    lane-ordered by a weighted-deficit schedule
    (``ZOO_SERVING_LANE_WEIGHTS``) with starvation protection; a
    partially-filled batch bucket accumulates up to
    ``ZOO_SERVING_MAX_WAIT_MS`` per lane before dispatching (continuous
    batching; default 0 keeps the legacy dispatch-every-read behavior);
    deadline-lapsed records get an explicit typed expired result; and an
    admission-control tick (``ZOO_SERVING_ADMISSION_S``) sheds NEW
    batch-lane enqueues at the broker while per-lane p99 burn says the
    path is saturated (docs/observability.md "Priority lanes & admission
    control").

    Autoregressive generate: a record enqueued with ``generate={...}``
    (InputQueue/frontend) carries its decode parameters on the trace
    side channel. On a scheduler-capable model (``decode_step_fn``, i.e.
    an InferenceModel) assembled generate records are handed to a
    persistent **step-level scheduler**
    (inference/decode_scheduler.py): live sequences advance one wide
    step per serve-loop turn over a shared paged KV pool, newly-arrived
    records admit mid-flight (chunked prefill), heterogeneous decode
    params share the wide step, and interactive encode batches
    interleave BETWEEN decode steps — a step is preempted
    (``zoo_decode_preemptions_total``) whenever a waiting encode lane
    outranks the live decode lanes on the weighted-deficit schedule,
    with a starvation floor so decode always advances. ``draft_model``
    adds speculative decoding (greedy output bitwise unchanged). Duck-
    typed models keep the legacy whole-batch decode loop: generate
    records batch with identical decode params only and run to
    completion in one dispatch.
    """

    #: consecutive full dequeues that count as "sustained backlog"
    BACKLOG_GROW_AFTER = 8
    #: consecutive under-half-full dequeues before stepping DOWN one rung
    #: (bounds pad waste after a burst; empty polls count as idle too)
    IDLE_SHRINK_AFTER = 32
    #: max entries one reclaim sweep claims — a crashed replica's whole
    #: pending set transfers in ONE XCLAIM (overflow feeds _claim_backlog)
    RECLAIM_BATCH = 256
    #: finished-entry-id ring size for the redelivery dedupe
    DEDUPE_WINDOW = 65536
    #: safety margin subtracted from a record's deadline when computing
    #: the partial-bucket dispatch trigger — dispatch BEFORE the deadline,
    #: not at it
    SLACK_MARGIN_S = 0.005
    #: the lane admission control sheds when per-lane SLO burn says the
    #: serving path is saturated; interactive/default always keep flowing
    ADMISSION_LANE = "batch"
    #: consecutive preempted decode ticks before a step runs regardless —
    #: encode pressure may slow decode, never starve it
    DECODE_STARVATION_FLOOR = 4
    #: count-shaped buckets for the step/page cost histograms (the
    #: latency default buckets top out at 30 — useless for step counts)
    COST_COUNT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                          256.0, 512.0, 1024.0, 4096.0)

    def __init__(self, model, broker_port: int, batch_size: int = 8,
                 stream: str = INPUT_STREAM, result_key: str = RESULT_HASH,
                 group: str = "serving", consumer: Optional[str] = None,
                 input_cols: Optional[List[str]] = None,
                 cipher: schema.Cipher = None,
                 postprocess=None, block_ms: int = 50,
                 claim_min_idle_ms: Optional[int] = None,
                 reclaim_interval_s: Optional[float] = None,
                 broker_host: str = "127.0.0.1",
                 image_preprocess=None,
                 pipeline_window: int = 2,
                 max_batch_size: Optional[int] = None,
                 min_batch_size: Optional[int] = None,
                 warmup: bool = True,
                 replica_id: Optional[str] = None,
                 draft_model=None, spec_k: int = 4):
        # before this process's first compile (a duck-typed model never
        # went through InferenceModel's constructor)
        compile_ahead.configure_persistent_cache()
        self.model = model
        self.batch_size = int(batch_size)
        self.pipeline_window = int(pipeline_window)
        self.max_batch_size = int(max_batch_size) if max_batch_size \
            else 4 * self.batch_size
        self.min_batch_size = int(min_batch_size) if min_batch_size \
            else self.batch_size
        # the bucket ladder spans shrink floor → growth cap; the starting
        # bucket snaps to a rung so every dispatch shape is a ladder shape
        self.ladder = compile_ahead.BucketLadder(
            min(self.min_batch_size, self.batch_size),
            max(self.max_batch_size, self.batch_size))
        self.batch_size = self.ladder.rung_for(self.batch_size)
        self._full_streak = 0
        self._idle_streak = 0
        # ZOO_WARMUP_BUCKETS: 0 disables compile-ahead warmup, N caps the
        # rung count (smallest first), unset warms the full ladder
        raw = os.environ.get("ZOO_WARMUP_BUCKETS", "").strip()
        self._warmup_enabled = bool(warmup) and raw != "0"
        limit = int(raw) if raw.isdigit() and int(raw) > 0 else None
        self._warm_rungs = self.ladder.rungs if limit is None \
            else self.ladder.rungs[:limit]
        self._warm_kicked = False
        self.broker_host = broker_host
        self.broker_port = broker_port
        self.stream, self.result_key = stream, result_key
        # fleet identity first: the default consumer id IS the replica id,
        # so N replicas sharing one group fan out with per-consumer leases
        # instead of all reading as "c0" (single-consumer legacy)
        self.replica_id = replica_id or fleet.default_replica_id(stream)
        self.group = group
        self.consumer = consumer or self.replica_id
        self.input_cols = input_cols
        self.cipher = cipher
        self.postprocess = postprocess
        self.image_preprocess = image_preprocess
        self.block_ms = block_ms
        # --- SLO-aware scheduling (priority lanes, continuous batching) —
        # ZOO_SERVING_MAX_WAIT_MS: how long a partially-filled batch
        # bucket may accumulate before it dispatches anyway, per lane
        # ("40" for all lanes, "interactive=5,batch=250" per-lane; default
        # 0 = dispatch every read immediately, the legacy behavior).
        self.max_wait_ms = _parse_lane_map(
            os.environ.get("ZOO_SERVING_MAX_WAIT_MS", ""),
            {lane: 0.0 for lane in schema.PRIORITIES})
        # ZOO_SERVING_LANE_WEIGHTS: weighted-deficit shares per lane —
        # the lane with the lowest served-records/weight ratio reads
        # first, so batch work always drains (starvation protection)
        # while interactive gets the biggest share under contention
        self.lane_weights = _parse_lane_map(
            os.environ.get("ZOO_SERVING_LANE_WEIGHTS", ""),
            {"interactive": 4.0, "default": 2.0, "batch": 1.0})
        self._lane_credit: Dict[str, float] = {
            lane: 0.0 for lane in schema.PRIORITIES}
        self._lanes_priority = ",".join(schema.PRIORITIES)
        # the assembly bucket: decoded records waiting to fill a batch —
        # (entry_id, uri, inputs, queue_meta, lane, t_arrive, t_deadline,
        #  gen) where gen is the normalized generate request or None
        self._asm: List[tuple] = []
        # ZOO_SERVING_DECODE_MAX_SEQ: when > 0 and the model supports
        # warm_decode, ladder warmup ALSO AOT-compiles the autoregressive
        # decode shapes — every (batch rung × seq-length rung up to this
        # many positions) pair — so a generate request's growing decoder
        # buffer swaps rungs without an in-band compile. 0 (default)
        # leaves decode shapes to compile on first use.
        raw = os.environ.get("ZOO_SERVING_DECODE_MAX_SEQ", "").strip()
        self._decode_max_seq = int(raw) if raw else 0
        # --- step-level decode (inference/decode_scheduler.py): built
        # lazily at the first generate admission on a scheduler-capable
        # model; duck-typed models keep the whole-batch _GenBatch path
        self._decode_sched: Optional[decode_scheduler.DecodeScheduler] = \
            None
        self._draft_model = draft_model
        self._spec_k = int(spec_k)
        # live sequence -> (uri, ack_cmd, queue-wait meta, lane, conn_gen)
        self._gen_live: Dict = {}
        self._decode_yield_streak = 0
        # ZOO_SERVING_ADMISSION_S: cadence of the admission-control tick
        # (SLO burn check + broker XSHED flip + lane depth gauges);
        # 0 disables admission control entirely
        raw = os.environ.get("ZOO_SERVING_ADMISSION_S", "").strip()
        self._admission_interval_s = float(raw) if raw else 1.0
        self._last_admission = 0.0
        # mirrors for /healthz and tests (read cross-thread under lock)
        self.admission_shedding = False
        self._admission_dirty = False
        self.records_expired = 0
        # the delivery lease: entries idle past this are claimable by any
        # OTHER consumer (at-least-once redelivery after a replica crash)
        if claim_min_idle_ms is None:
            raw = os.environ.get("ZOO_SERVING_LEASE_MS", "").strip()
            claim_min_idle_ms = int(raw) if raw else 30000
        self.claim_min_idle_ms = int(claim_min_idle_ms)
        # claim at most ~1/s by default — recovery is a rare path, the hot
        # read loop must not pay a broker round-trip per poll
        if reclaim_interval_s is None:
            raw = os.environ.get("ZOO_SERVING_RECLAIM_S", "").strip()
            reclaim_interval_s = float(raw) if raw \
                else max(0.5, self.claim_min_idle_ms / 2000.0)
        self._claim_interval_s = float(reclaim_interval_s)
        self._last_claim = 0.0
        # supervisor-thread → serve-thread "sweep now" signal (Event: the
        # rate-limiter clock itself stays serve-thread-confined)
        self._reclaim_asap = threading.Event()
        # one reclaim sweep claims every expired lease in a single XCLAIM
        # (up to RECLAIM_BATCH); beyond-batch entries queue here and feed
        # subsequent dispatches, so "sweeps fired" stays 1 per crash
        self._claim_backlog: Deque[Tuple[int, str, str]] = \
            collections.deque()
        # entry-id dedupe ring: ids in flight or already finished by THIS
        # consumer are dropped on re-arrival, making result writes
        # idempotent under at-least-once redelivery. Serve-thread only.
        self._inflight_ids: set = set()
        self._done_ids: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        # broker connection generation: a redial invalidates the dedupe
        # ring (a restarted broker reuses entry ids from 1)
        self._conn_gen = 0
        self._seen_client_gen = 0
        self.timer = StageTimer()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # records_out is bumped on the serving thread and read from
        # metrics() on arbitrary caller threads; += is not atomic
        self._state_lock = threading.Lock()
        self.records_out = 0
        # process-wide telemetry: the registry counters feed the Prometheus
        # /metrics exposition; traces are keyed by record uri so one
        # record's latency decomposes into engine stages (sampled per
        # batch at the tracer's rate, default ZOO_TELEMETRY_SAMPLE=1.0)
        self._tracer = telemetry.get_tracer()
        reg = telemetry.get_registry()
        self._rec_counter = reg.counter(
            "zoo_serving_records_total",
            "Records with a flushed result", ("stream",)).labels(stream)
        self._err_counter = reg.counter(
            "zoo_serving_record_errors_total",
            "Records that got an error result", ("stream",)).labels(stream)
        self._batch_gauge = reg.gauge(
            "zoo_serving_batch_bucket",
            "Current adaptive compile-bucket batch size",
            ("stream",)).labels(stream)
        self._batch_gauge.set(self.batch_size)
        # first-class queue wait + end-to-end latency (ISSUE 6): stamped
        # client-side (schema trace meta), measured here — the fleet's
        # backlog signal and the SLO monitor's p99 source
        self._wait_hist = reg.histogram(
            "zoo_queue_wait_seconds",
            "Broker queue wait: client enqueue to engine dequeue",
            ("stream",)).labels(stream)
        # per-PRIORITY end-to-end latency: the per-lane SLOs in
        # common/slo.py filter on the priority label, and the admission
        # tick sheds the batch lane off these very histograms
        lat = reg.histogram(
            "zoo_serving_latency_seconds",
            "End-to-end record latency: client enqueue to result flush",
            ("stream", "priority"))
        self._latency_hist = {lane: lat.labels(stream, lane)
                              for lane in schema.PRIORITIES}
        # zero-silent-drops ledger, expired leg (shed is counted client-
        # side in InputQueue — a refused XADD never reaches the engine)
        exp = reg.counter(
            "zoo_serving_expired_total",
            "Records whose deadline_ms lapsed before inference; each got "
            "an explicit expired result", ("stream", "priority"))
        self._expired_counter = {lane: exp.labels(stream, lane)
                                 for lane in schema.PRIORITIES}
        depth = reg.gauge(
            "zoo_serving_lane_depth",
            "Broker queue depth per priority lane",
            ("stream", "priority"))
        self._lane_depth_gauge = {lane: depth.labels(stream, lane)
                                  for lane in schema.PRIORITIES}
        self._admission_gauge = reg.gauge(
            "zoo_serving_admission_state",
            "1 while admission control is shedding the batch lane",
            ("stream", "priority")).labels(stream, self.ADMISSION_LANE)
        # at-least-once delivery observability: redeliveries received via
        # XCLAIM and the reclaim sweeps that produced them
        self._redeliver_counter = reg.counter(
            "zoo_serving_redelivered_total",
            "Entries re-delivered via lease reclaim (XCLAIM)",
            ("stream",)).labels(stream)
        self._reclaim_counter = reg.counter(
            "zoo_serving_lease_reclaims_total",
            "Reclaim sweeps that claimed at least one expired lease",
            ("stream",)).labels(stream)
        self._preempt_counter = reg.counter(
            "zoo_decode_preemptions_total",
            "Decode scheduler steps deferred because a waiting encode "
            "lane outranked the live decode lanes on the weighted-"
            "deficit schedule", ("stream",)).labels(stream)
        # per-request cost attribution (ISSUE 17): settled when a record's
        # result flushes — an encode record is billed its share of the
        # batch's device time; a generate record its accumulated share of
        # every wide decode step it rode, plus steps and KV pages held —
        # so each lane gets a measured unit cost
        cost_dev = reg.histogram(
            "zoo_request_cost_device_seconds",
            "Device-seconds attributed to one record at settlement",
            ("stream", "priority", "kind"))
        cost_steps = reg.histogram(
            "zoo_request_cost_decode_steps",
            "Decode steps one generate record consumed",
            ("stream", "priority", "kind"), buckets=self.COST_COUNT_BUCKETS)
        cost_pages = reg.histogram(
            "zoo_request_cost_kv_pages",
            "KV cache pages one generate record held at retirement",
            ("stream", "priority", "kind"), buckets=self.COST_COUNT_BUCKETS)
        self._cost_device_hist = {
            (lane, kind): cost_dev.labels(stream, lane, kind)
            for lane in schema.PRIORITIES
            for kind in ("encode", "generate")}
        self._cost_steps_hist = {
            lane: cost_steps.labels(stream, lane, "generate")
            for lane in schema.PRIORITIES}
        self._cost_pages_hist = {
            lane: cost_pages.labels(stream, lane, "generate")
            for lane in schema.PRIORITIES}
        # cross-thread-readable mirrors for /healthz and tests
        self.records_redelivered = 0
        self.lease_reclaims = 0
        # fleet identity heartbeats ride the broker hash so any frontend
        # can enumerate live replicas (common/fleet.py); the frontend
        # fills in the advertised metrics host/port at start()
        self._advertise = ("127.0.0.1", 0)
        self._started_wall = 0.0
        self._heartbeater: Optional[fleet.Heartbeater] = None
        self._replica_supervisor: Optional[fleet.ReplicaSupervisor] = None
        # wedge failover (ISSUE 7): with ZOO_CPU_FALLBACK=1 a backend-loss
        # error drains the window onto pre-built CPU executables and keeps
        # serving degraded until the supervisor reports recovered. The
        # flag/t0/seconds are written on the serve thread and read from
        # frontend/test threads — all under _state_lock.
        self._cpu_fallback = resilience.cpu_fallback_enabled()
        self._supervisor: Optional[resilience.BackendSupervisor] = None
        self._failover = False
        self._failover_t0: Optional[float] = None
        self.failover_seconds: List[float] = []

    def _decode_images(self, inputs):
        """Decode any raw-image entries and run the preprocessing chain
        (ref PreProcessing.scala:67-90: bytes -> mat -> configured
        resize/crop/normalize -> tensor)."""
        out = {}
        for k, v in inputs.items():
            if isinstance(v, schema.ImageBytes):
                import io

                from PIL import Image
                arr = np.asarray(
                    Image.open(io.BytesIO(v.data)).convert("RGB"),
                    np.float32)
                if self.image_preprocess is not None:
                    arr = self.image_preprocess(arr)
                v = np.asarray(arr, np.float32)
            out[k] = v
        return out

    # --------------------------------------------------- lane scheduling
    def _lane_order(self) -> str:
        """Comma-joined lane preference for the next read — weighted-
        deficit scheduling. Each lane accrues one credit per record it got
        served; the lane with the lowest credit/weight ratio reads first.
        Under sustained contention lanes converge on their weight shares
        (default 4:2:1), and a lane that has been skipped drifts to the
        lowest ratio and MUST read next — batch work always drains."""
        ratios = {lane: self._lane_credit.get(lane, 0.0)
                  / max(self.lane_weights.get(lane, 1.0), 1e-9)
                  for lane in schema.PRIORITIES}
        base = min(ratios.values())
        if base > 0:
            # renormalize so the minimum ratio is 0 — credits stay bounded
            # over long runs without changing the relative order
            for lane in self._lane_credit:
                self._lane_credit[lane] = max(
                    0.0, self._lane_credit[lane] - base
                    * max(self.lane_weights.get(lane, 1.0), 1e-9))
        order = sorted(schema.PRIORITIES,
                       key=lambda l: (ratios[l],
                                      schema.PRIORITIES.index(l)))
        return ",".join(order)

    def _asm_trigger(self) -> float:
        """perf_counter time at which the assembly bucket must dispatch
        even partially filled: the oldest member's lane max-wait cap,
        tightened by any member whose deadline slack is about to run
        out. With the default max_wait of 0 this is the arrival time
        itself — every read dispatches immediately (legacy behavior)."""
        t = float("inf")
        for _eid, _uri, _inputs, _m, lane, t_arr, t_deadline, _g \
                in self._asm:
            t = min(t, t_arr + self.max_wait_ms.get(lane, 0.0) / 1000.0)
            if t_deadline is not None:
                t = min(t, max(t_arr, t_deadline - self.SLACK_MARGIN_S))
        return t

    def _expire_record(self, uri: str, lane: str, cmds: list):
        """A record's ``deadline_ms`` lapsed before inference: store an
        explicit typed expired result — never a silent drop; the client's
        poll raises DeadlineExpiredError instead of timing out — and
        count it per lane, disjoint from the error counter."""
        cmds.append(("HSET", self.result_key, uri, schema.encode_error(
            "deadline_ms expired before the engine served the record",
            self.cipher, code="expired")))
        self._expired_counter.get(
            lane, self._expired_counter[schema.DEFAULT_PRIORITY]).inc()
        with self._state_lock:
            self.records_expired += 1

    # --------------------------------------------------------------- loop
    def _produce(self, client: BrokerClient, block_ms: int):
        """Host stage: dequeue + decode + preprocess + stack/pad ONE batch.
        Returns ``(x, ctx)`` ready for dispatch, or None when nothing
        servable arrived (per-record errors are flushed here).

        Continuous batching: decoded records accumulate in the assembly
        bucket ``_asm``; the bucket dispatches when it fills, when the
        oldest member has waited out its lane's ``ZOO_SERVING_MAX_WAIT_MS``
        (default 0 — every read dispatches immediately), or when any
        member's deadline slack runs out (``_asm_trigger``). Reads and
        reclaims are lane-ordered by the weighted-deficit schedule."""
        t_dq0 = time.perf_counter()
        # recover entries a dead/crashed consumer never acked (ref: the
        # Redis-streams recovery path the reference LACKS an analog of —
        # XPENDING counts them but they were lost forever; here XCLAIM
        # re-delivers another consumer's entries once their delivery lease
        # has been idle claim_min_idle_ms). Rate-limited: recovery polling
        # must not tax the hot read loop. One sweep claims EVERY expired
        # lease (up to RECLAIM_BATCH); the overflow queues in
        # _claim_backlog and feeds the next dispatches.
        # All stage timing is on the monotonic perf_counter clock — wall-
        # clock stamps let NTP slew corrupt stage stats AND the claim-
        # interval rate limiter.
        entries = []
        room = max(0, self.batch_size - len(self._asm))
        if self._claim_backlog:
            while self._claim_backlog and len(entries) < room:
                entries.append(self._claim_backlog.popleft())
        elif self._reclaim_asap.is_set() or \
                t_dq0 - self._last_claim >= self._claim_interval_s:
            self._reclaim_asap.clear()
            self._last_claim = t_dq0
            # lane-ordered reclaim: a dead peer's INTERACTIVE pending
            # entries re-deliver before its batch-lane entries
            claimed = client.xclaim(self.stream, self.group, self.consumer,
                                    self.claim_min_idle_ms,
                                    self.RECLAIM_BATCH,
                                    lanes=self._lanes_priority)
            if claimed:
                self._redeliver_counter.inc(len(claimed))
                self._reclaim_counter.inc()
                with self._state_lock:
                    self.records_redelivered += len(claimed)
                    self.lease_reclaims += 1
                logger.warning("lease reclaim: %d orphaned entries "
                               "re-delivered to %s", len(claimed),
                               self.consumer)
                entries = claimed[:room]
                self._claim_backlog.extend(claimed[room:])
        if not entries and room > 0:
            eff_block = block_ms
            if self._asm:
                # an armed bucket bounds the blocking read: never sleep
                # past the dispatch trigger of records already waiting
                left_ms = (self._asm_trigger() - t_dq0) * 1000.0
                eff_block = int(min(block_ms, max(0.0, left_ms)))
            entries = client.xreadgroup(self.group, self.consumer,
                                        self.stream, room, eff_block,
                                        lanes=self._lane_order())
        # the client may have transparently redialed inside xclaim/
        # xreadgroup (BrokerClient retry): the peer could be a RESTARTED
        # broker reusing entry ids from 1, so the dedupe ring must reset
        # BEFORE it classifies this read's ids
        cgen = getattr(client, "generation", 0)
        if cgen != self._seen_client_gen:
            self._seen_client_gen = cgen
            self._conn_gen += 1
            self._inflight_ids.clear()
            self._done_ids.clear()
            self._claim_backlog.clear()
            # the bucket's entry ids describe the dead connection too; its
            # records re-deliver via their lease like any unacked entry
            self._asm.clear()
            self._abort_decode()
        # idempotence under redelivery: an id this consumer already has in
        # flight (or has finished this connection) is dropped, so a
        # double-delivered record can never double-count or double-write.
        # Already-done ids get their (lost) ack replayed instead.
        if entries:
            fresh, stale_acks = [], []
            for eid, lane, payload in entries:
                if eid in self._done_ids:
                    stale_acks.append(
                        ("XACK", self.stream, self.group, str(eid)))
                elif eid not in self._inflight_ids:
                    self._inflight_ids.add(eid)
                    fresh.append((eid, lane, payload))
            if stale_acks:
                client.pipeline(stale_acks)
            entries = fresh
        read_n = len(entries)
        t_dq1 = time.perf_counter()
        if read_n:
            self.timer.record("dequeue", t_dq1 - t_dq0)

        t0 = time.perf_counter()
        # intake: decode each fresh entry. Records that terminate HERE
        # (undecodable / image-decode failure / deadline already lapsed)
        # flush their result+ack NOW instead of riding the bucket; the
        # rest join the assembly bucket and bump their lane's deficit
        # credit. Pipelined flush — per-record round-trips dominated host
        # time at large batch sizes.
        term_cmds: list = []
        term_acks: list = []
        for eid, lane, payload in entries:
            ack = ("XACK", self.stream, self.group, str(eid))
            # one bad record (corrupt b64, wrong cipher, bad uri) must not
            # take the batch or the serve loop down: store an error result
            # for it and continue
            try:
                uri, inputs, meta = schema.decode_record_meta(
                    payload, self.cipher)
                schema.validate_uri(uri)
            except Exception as e:
                logger.warning("dropping undecodable record %s: %s", eid, e)
                term_acks.append(ack)
                continue
            try:
                inputs = self._decode_images(inputs)
            except Exception as e:
                # the uri is known: the client gets a real error result
                # (ref stores per-record errors the same way)
                term_cmds.append((
                    "HSET", self.result_key, uri,
                    schema.encode_error(
                        f"image decode failed: {e}", self.cipher)))
                self._err_counter.inc()
                term_acks.append(ack)
                continue
            # from here to the bucket append the eid is in _inflight_ids
            # but not yet settled: an exception escaping to _run's
            # catch-all would strand it — redeliveries of the id are
            # dropped by the dedupe ring while the entry itself is never
            # acked or served, re-pending until a reconnect. Terminate
            # the record instead: typed error + ack, like any bad record.
            try:
                m = self._queue_wait(meta, t_dq1)
                t_deadline = None
                d = meta.get("d") if isinstance(meta, dict) else None
                if isinstance(d, (int, float)) and d > 0 and m is not None:
                    # deadline is relative to the client's enqueue stamp,
                    # already mapped onto this clock by _queue_wait
                    t_deadline = m[0] + d / 1000.0
                if t_deadline is not None and t_dq1 >= t_deadline:
                    self._expire_record(uri, lane, term_cmds)
                    term_acks.append(ack)
                    continue
                # generate side channel: re-validated at intake so a hand-
                # rolled record with junk decode params errors HERE, typed,
                # instead of blowing up the device batch
                try:
                    g = schema.validate_generate(
                        meta.get("g") if isinstance(meta, dict) else None)
                except ValueError as e:
                    term_cmds.append((
                        "HSET", self.result_key, uri, schema.encode_error(
                            f"bad generate request: {e}", self.cipher)))
                    self._err_counter.inc()
                    term_acks.append(ack)
                    continue
                self._lane_credit[lane] = \
                    self._lane_credit.get(lane, 0.0) + 1.0
                self._asm.append((eid, uri, inputs, m, lane, t_dq1,
                                  t_deadline, g))
            except Exception as e:
                logger.exception("record intake failed for %s", eid)
                term_cmds.append((
                    "HSET", self.result_key, uri, schema.encode_error(
                        f"record intake failed: {e}", self.cipher)))
                self._err_counter.inc()
                term_acks.append(ack)
                continue
        if term_acks or term_cmds:
            client.pipeline(term_cmds + term_acks)
            self._mark_done(term_acks, self._conn_gen)

        # dispatch decision: full bucket, or the max-wait/deadline trigger
        # of the waiting members has passed
        now = time.perf_counter()
        if not self._asm:
            if read_n == 0:
                # an empty poll with an empty bucket is the strongest idle
                # signal there is — it feeds the same streak accounting as
                # an under-half-full batch
                self._grow_batch_on_backlog(0)
            return None
        if len(self._asm) < self.batch_size and now < self._asm_trigger():
            return None                          # keep accumulating
        take = self._asm[:self.batch_size]
        self._asm = self._asm[self.batch_size:]
        self._grow_batch_on_backlog(len(take))

        # step-level decode handoff: on a scheduler-capable model the
        # assembled generate records go straight to the persistent
        # scheduler (heterogeneous decode params welcome — they share
        # the wide step) and only the plain-predict remainder dispatches
        # as a device batch. Page-pool admission control may bounce a
        # record back to the bucket's head, still un-acked, to retry
        # once a live sequence retires.
        if getattr(self.model, "decode_step_fn", None) is not None:
            gen_take = [e for e in take if e[7] is not None]
            if gen_take:
                take = [e for e in take if e[7] is None]
                self._admit_generate(client, gen_take)
                if not take:
                    return None

        # generate and plain-predict records never share a device batch
        # (different executables, different result shapes), and generate
        # records only batch with identical decode params. Dispatch the
        # largest kind now; the rest go back to the bucket's head — still
        # un-acked, keeping their lease and arrival stamps, so progress
        # is guaranteed (every turn serves at least one kind)
        kinds: Dict = {}
        for e in take:
            key = tuple(sorted(e[7].items())) if e[7] is not None else None
            kinds.setdefault(key, []).append(e)
        best_kind = max(kinds, key=lambda k: len(kinds[k]))
        if len(kinds) > 1:
            self._asm = [e for k, members in kinds.items()
                         if k != best_kind for e in members] + self._asm
            take = kinds[best_kind]
        gen_params = dict(best_kind) if best_kind is not None else None

        err_cmds: list = []
        ack_cmds = []
        uris, rows, metas = [], [], []
        for eid, uri, inputs, m, lane, _t_arr, t_deadline, _g in take:
            ack_cmds.append(("XACK", self.stream, self.group, str(eid)))
            if t_deadline is not None and now >= t_deadline:
                # expired while waiting in the bucket
                self._expire_record(uri, lane, err_cmds)
                continue
            uris.append(uri)
            rows.append(inputs)
            metas.append((m, lane))
        if rows:
            # batch by the MAJORITY shape signature — a single malformed
            # leading record must not reject the whole batch
            sig = lambda r: tuple(sorted(  # noqa: E731
                (k, np.shape(v)) for k, v in r.items()))
            counts: Dict = {}
            for r in rows:
                counts[sig(r)] = counts.get(sig(r), 0) + 1
            best = max(counts, key=lambda s: counts[s])
            kept_uris, kept, kept_metas = [], [], []
            for uri, r, m in zip(uris, rows, metas):
                if sig(r) == best:
                    kept_uris.append(uri)
                    kept.append(r)
                    kept_metas.append(m)
                else:
                    err_cmds.append((
                        "HSET", self.result_key, uri, schema.encode_error(
                            f"tensor shapes {dict(best)} expected, got "
                            f"{ {k: np.shape(v) for k, v in r.items()} }",
                            self.cipher)))
                    self._err_counter.inc()
            uris, rows, metas = kept_uris, kept, kept_metas
        if not rows:
            client.pipeline(err_cmds + ack_cmds)
            self._mark_done(ack_cmds, self._conn_gen)
            return None
        n = len(rows)
        sampled = self._tracer.should_sample()
        if gen_params is not None:
            # generate batch: the record's "start" tensor seeds the
            # decoder, its remaining tensor feeds the encoder prefill;
            # both pad to the batch rung so prefill rides the same
            # pre-compiled (sharded) rungs as plain predicts
            bad = None
            if "start" not in rows[0]:
                bad = "generate records need a 'start' input tensor"
            elif len(rows[0]) != 2:
                bad = ("generate records carry exactly two inputs: the "
                       "encoder tensor and 'start'")
            if bad is not None:
                for uri in uris:
                    err_cmds.append((
                        "HSET", self.result_key, uri,
                        schema.encode_error(bad, self.cipher)))
                    self._err_counter.inc()
                client.pipeline(err_cmds + ack_cmds)
                self._mark_done(ack_cmds, self._conn_gen)
                return None
            enc_col = next(k for k in sorted(rows[0]) if k != "start")
            rung = min(self.ladder.rung_for(n), self.batch_size)
            enc, start = list(compile_ahead.pad_to_rung(
                [np.stack([r[enc_col] for r in rows]),
                 np.stack([r["start"] for r in rows])],
                rung, site="serving"))
            x = _GenBatch(enc, start, gen_params,
                          tuple(uris) if sampled else ())
        else:
            cols = self.input_cols or sorted(rows[0].keys())
            batch = [np.stack([r[c] for r in rows]) for c in cols]
            # pad to the nearest ladder rung at or below the current
            # bucket — a short dequeue rides a smaller pre-compiled
            # executable instead of padding all the way up
            # (zoo_bucket_pad_fraction is the waste)
            rung = min(self.ladder.rung_for(n), self.batch_size)
            batch = list(compile_ahead.pad_to_rung(batch, rung,
                                                   site="serving"))
            x = batch[0] if len(batch) == 1 else tuple(batch)
        t_pp1 = time.perf_counter()
        self.timer.record("preprocess", t_pp1 - t0)
        # trace=(dequeue start/end, preprocess start/end) when this batch
        # is sampled — _finish turns the stamps plus the Completed's
        # dispatch/device timing into per-uri spans
        trace = (t_dq0, t_dq1, t0, t_pp1) if sampled else None
        # x rides the ctx too so a backend-lost batch can be re-dispatched
        # on the CPU fallback at retire time (_failover_redispatch); the
        # connection generation gates the dedupe bookkeeping in _finish
        return x, (uris, err_cmds, ack_cmds, n, trace, metas, x,
                   self._conn_gen)

    def _mark_done(self, ack_cmds, gen: int):
        """Move a flushed batch's entry ids from in-flight to the bounded
        done ring (serve-thread only). ``gen`` guards against a batch that
        straddled a broker reconnect poisoning the fresh ring — a
        restarted broker reuses entry ids from 1."""
        if gen != self._conn_gen:
            return
        for c in ack_cmds:
            eid = int(c[3])
            self._inflight_ids.discard(eid)
            self._done_ids[eid] = None
        while len(self._done_ids) > self.DEDUPE_WINDOW:
            self._done_ids.popitem(last=False)

    def _queue_wait(self, meta, t_dq1: float):
        """Measure one record's broker queue wait from its client stamp.
        Returns ``(t_enqueue_on_this_clock, wait_s)`` or None (no stamp).

        The stamp is dual-clock: ``t_pc`` (perf_counter, CLOCK_MONOTONIC —
        directly comparable across processes on one Linux host) is used
        when the delta is plausible (0..1h); otherwise the wall-clock
        stamp covers cross-host clients, clamped at 0 so NTP slew can
        only blur a wait, never fabricate a negative one."""
        if not isinstance(meta, dict) or not meta:
            return None
        wait = None
        t_pc = meta.get("t_pc")
        if isinstance(t_pc, (int, float)):
            d = t_dq1 - float(t_pc)
            if 0.0 <= d < 3600.0:
                wait = d
        if wait is None:
            t_wall = meta.get("t_wall")
            if isinstance(t_wall, (int, float)):
                now = time.time()  # zoolint: disable=wallclock-hotpath
                wait = min(max(0.0, now - float(t_wall)), 3600.0)
        if wait is None:
            return None
        self._wait_hist.observe(wait)
        return (t_dq1 - wait, wait)

    def _grow_batch_on_backlog(self, dequeued: int):
        """Adaptive batch-bucket stepping, both directions. Every dequeue
        coming back full means the stream is producing faster than we
        drain — step up one ladder rung (capped at ``max_batch_size``).
        With warmup on, growth is gated on the next rung's executable
        being built already: the swap is stall-free, and an unready rung
        pins the streak and (re-)kicks its background compile instead of
        compiling in-band on the serve thread. Sustained under-half-full
        dequeues (empty polls included) step back DOWN one rung after
        ``IDLE_SHRINK_AFTER`` turns, bounding pad waste after a burst."""
        if dequeued >= self.batch_size:
            self._full_streak += 1
            self._idle_streak = 0
        elif dequeued * 2 < self.batch_size:
            self._full_streak = 0
            self._idle_streak += 1
        else:
            self._full_streak = 0
            self._idle_streak = 0
        if (self._full_streak >= self.BACKLOG_GROW_AFTER
                and self.batch_size < self.max_batch_size):
            nxt = self.ladder.up(self.batch_size)
            if not self._rung_ready(nxt):
                # hold the current rung until the background compile
                # lands — swapping now would stall the serve thread on an
                # XLA compile exactly when backlog is highest
                self._full_streak = self.BACKLOG_GROW_AFTER
                self._warm_rung(nxt)
                return
            self._set_bucket(nxt, "sustained backlog")
        elif (self._idle_streak >= self.IDLE_SHRINK_AFTER
                and self.batch_size > self.min_batch_size):
            self._set_bucket(self.ladder.down(self.batch_size),
                             "sustained idle")

    def _set_bucket(self, rung: int, why: str):
        """One bucket transition: reset both streaks, record the new size
        on the ``batch_size`` timer series and the serving gauge."""
        self.batch_size = int(rung)
        self._full_streak = 0
        self._idle_streak = 0
        self.timer.record_value("batch_size", self.batch_size)
        self._batch_gauge.set(self.batch_size)
        logger.info("%s: batch bucket -> %d", why, self.batch_size)

    def _rung_ready(self, rung: int) -> bool:
        """Whether switching to ``rung`` is a stall-free swap. Duck-typed
        models (no AOT cache) and warmup-disabled engines always read
        ready — that is the legacy in-band-recompile behavior."""
        fn = getattr(self.model, "rung_ready", None)
        if fn is None or not self._warmup_enabled:
            return True
        try:
            return bool(fn(rung))
        except Exception:
            return True

    def _warm_rung(self, rung: int):
        """Kick a background AOT compile of one rung (growth found it
        cold — e.g. ``ZOO_WARMUP_BUCKETS`` capped the initial warmup)."""
        fn = getattr(self.model, "warm_up", None)
        if fn is not None:
            try:
                fn(rungs=(rung,))
            except Exception:
                logger.debug("rung %d warmup kick failed", rung,
                             exc_info=True)

    def _kick_warmup(self) -> bool:
        """Attach the ladder to the model and start the background AOT
        warmup over ``self._warm_rungs``. Returns False (and stays
        re-kickable from the serve loop) only when the model supports
        warmup but cannot describe its input shapes yet."""
        set_ladder = getattr(self.model, "set_ladder", None)
        warm_up = getattr(self.model, "warm_up", None)
        if set_ladder is None or warm_up is None:
            self._warm_kicked = True   # duck-typed model: nothing to warm
            return False
        try:
            set_ladder(self.ladder)
            has_spec = getattr(self.model, "has_warm_spec", None)
            if has_spec is not None and not has_spec():
                return False           # retry once the model is loaded
            warm_up(rungs=list(self._warm_rungs))
            self._kick_decode_warmup()
            self._warm_kicked = True
            return True
        except Exception:
            logger.exception("ladder warmup failed; serving continues "
                             "with in-band compiles")
            self._warm_kicked = True
            return False

    def _kick_decode_warmup(self):
        """AOT-warm the autoregressive decode rungs too
        (``ZOO_SERVING_DECODE_MAX_SEQ`` > 0 and the model supports
        ``warm_decode``): every batch-rung × seq-length-rung pair
        compiles in the background, so a generate request's growing
        decoder buffer swaps rungs without an in-band compile."""
        if self._decode_max_seq <= 0:
            return
        fn = getattr(self.model, "warm_decode", None)
        if fn is None:
            return
        kw = {}
        if hasattr(self.model, "paged_decode_step_fn"):
            # warm the paged step executables on the same grid, sized the
            # way the scheduler's lazily-built allocator will size the
            # pool — the first live paged dispatch then hits a built shape
            kw["paged_pool"] = (
                decode_scheduler.default_pool_pages(
                    self.max_batch_size,
                    self._decode_max_seq or generation.DEFAULT_SEQ_RUNGS[1],
                    spec_k=self._spec_k),
                generation.DEFAULT_SEQ_RUNGS[0])
        try:
            # a configured draft model means verify steps run k positions
            # past the live length — warm those taller rungs too
            fn(self._decode_max_seq, rungs=list(self._warm_rungs),
               verify_k=(self._spec_k if self._draft_model is not None
                         else 0), **kw)
        except TypeError:
            fn(self._decode_max_seq, rungs=list(self._warm_rungs))
        except Exception:
            logger.debug("decode warmup kick failed", exc_info=True)

    def wait_warm(self, timeout: Optional[float] = None
                  ) -> "ClusterServing":
        """Block until the background ladder compiles finish (tests,
        chip_smoke.py and the benchmark's serve drivers; no-op for
        duck-typed models)."""
        fn = getattr(self.model, "wait_warm", None)
        if fn is not None:
            fn(timeout=timeout)
        return self

    def _dispatch(self, x):
        """Device stage: non-blocking when the model supports it (an
        InferenceModel dispatches the jitted executable and returns device
        futures); duck-typed models fall back to their blocking predict.
        While failover is active, dispatch routes to the pre-built CPU
        rung instead — synchronous by nature, the host result rides the
        pipeline window as-is."""
        if isinstance(x, _GenBatch):
            return self._dispatch_generate(x)
        if self.failover_active:
            cpu_predict = getattr(self.model, "predict_cpu", None)
            if cpu_predict is not None:
                return cpu_predict(x)
        fn = getattr(self.model, "predict_async", None)
        return fn(x) if fn is not None else self.model.predict(x)

    def _dispatch_generate(self, gb: "_GenBatch"):
        """Run one generate batch's decode loop: (sharded) AOT prefill
        plus ``n`` bucketed decode steps (inference/generation.py).
        Synchronous by nature — every step feeds the previous step's
        output back — so the host ``[batch, steps, dim]`` result rides
        the pipeline window as-is, like the CPU-failover path. Sampled
        batches pass their uris through as decode-span trace ids."""
        p = gb.params
        n = int(p.get("n", 16))
        kw = dict(mode=p.get("m", "greedy"),
                  temperature=float(p.get("t", 1.0)), seed=p.get("s"))
        fn = getattr(self.model, "generate", None)
        if fn is not None:
            return fn(gb.enc, gb.start, n, trace_ids=gb.trace_uris, **kw)
        fn = getattr(self.model, "infer", None)
        if fn is not None:       # duck-typed zoo model (e.g. Seq2Seq)
            return fn(gb.enc, gb.start, n + 1, **kw)
        raise TypeError("model supports neither generate() nor infer() — "
                        "generate records need an autoregressive model")

    def _fetch(self, pending):
        fn = getattr(self.model, "predict_fetch", None)
        return np.asarray(fn(pending) if fn is not None else pending)

    # --------------------------------------------- step-level decode
    def _ensure_scheduler(self) -> decode_scheduler.DecodeScheduler:
        """The persistent step scheduler, built at the first generate
        admission: the page pool sizes off this engine's batch ladder ×
        the decode seq grid (``ZOO_SERVING_DECODE_MAX_SEQ``, falling back
        to the default seq-ladder top)."""
        if self._decode_sched is None:
            draft_fn = None
            if self._draft_model is not None:
                draft_fn = (self._draft_model.decode_step_fn()
                            if hasattr(self._draft_model, "decode_step_fn")
                            else self._draft_model)
            paged_fn = None
            make_paged = getattr(self.model, "paged_decode_step_fn", None)
            if make_paged is not None:
                try:
                    paged_fn = make_paged()
                except Exception:
                    logger.debug("paged decode seam unavailable",
                                 exc_info=True)
            sched = decode_scheduler.DecodeScheduler(
                self.model.decode_step_fn(),
                max_batch=self.max_batch_size,
                max_seq=(self._decode_max_seq
                         or generation.DEFAULT_SEQ_RUNGS[1]),
                batch_ladder=self.ladder,
                draft_fn=draft_fn, spec_k=self._spec_k,
                paged_step_fn=paged_fn)
            # published under the state lock: /healthz's decode_state()
            # reads the attribute from the HTTP thread
            with self._state_lock:
                self._decode_sched = sched
        return self._decode_sched

    def _admit_generate(self, client: BrokerClient, entries: List[tuple]):
        """Hand assembled generate records to the step scheduler. Each
        entry settles right here: expired/malformed records flush a typed
        result + ack now; admitted ones park their ack in ``_gen_live``
        until the sequence retires (``_finish_decode``); a record the
        page pool cannot hold yet goes back to the bucket's head,
        un-acked, to retry after the next retirement."""
        sched = self._ensure_scheduler()
        now = time.perf_counter()
        term_cmds: list = []
        term_acks: list = []
        back: list = []
        for entry in entries:
            eid, uri, inputs, m, lane, _t_arr, t_deadline, g = entry
            ack = ("XACK", self.stream, self.group, str(eid))
            if t_deadline is not None and now >= t_deadline:
                self._expire_record(uri, lane, term_cmds)
                term_acks.append(ack)
                continue
            bad = None
            if "start" not in inputs:
                bad = "generate records need a 'start' input tensor"
            elif len(inputs) != 2:
                bad = ("generate records carry exactly two inputs: the "
                       "encoder tensor and 'start'")
            if bad is not None:
                term_cmds.append((
                    "HSET", self.result_key, uri,
                    schema.encode_error(bad, self.cipher)))
                self._err_counter.inc()
                term_acks.append(ack)
                continue
            enc_col = next(k for k in sorted(inputs) if k != "start")
            try:
                seq = sched.admit(
                    np.asarray(inputs[enc_col]),
                    np.asarray(inputs["start"], np.float32),
                    int(g.get("n", 16)), mode=g.get("m", "greedy"),
                    temperature=float(g.get("t", 1.0)), seed=g.get("s"),
                    tag=uri, lane=lane,
                    trace_uri=(uri if self._tracer.should_sample()
                               else None))
            except decode_scheduler.PagePoolExhausted:
                back.append(entry)
                continue
            except Exception as e:
                term_cmds.append((
                    "HSET", self.result_key, uri, schema.encode_error(
                        f"generate admission failed: {e}", self.cipher)))
                self._err_counter.inc()
                term_acks.append(ack)
                continue
            self._gen_live[seq] = (uri, ack, m, lane, self._conn_gen)
        if back:
            self._asm = back + self._asm
        if term_acks or term_cmds:
            client.pipeline(term_cmds + term_acks)
            self._mark_done(term_acks, self._conn_gen)

    def _decode_should_yield(self) -> bool:
        """Per-step lane preemption, honoring the same weighted-deficit
        order reads use: defer this decode step when records WAITING in
        the assembly bucket belong to a lane with a strictly lower
        credit/weight ratio than every lane currently decoding — the
        device stays free for the imminent encode dispatch. The
        starvation floor guarantees a step runs after
        ``DECODE_STARVATION_FLOOR`` consecutive deferrals."""
        if self._decode_yield_streak >= self.DECODE_STARVATION_FLOOR:
            return False
        if not self._asm or not self._gen_live:
            return False

        def ratio(lane):
            return (self._lane_credit.get(lane, 0.0)
                    / max(self.lane_weights.get(lane, 1.0), 1e-9))

        waiting = min(ratio(e[4]) for e in self._asm)
        live = min(ratio(info[3]) for info in self._gen_live.values())
        return waiting < live

    def _decode_tick(self, client: BrokerClient) -> int:
        """One serve-loop turn's decode slice: run (or preempt) exactly
        one scheduler step and flush whatever finished. Encode batches
        interleave between these steps instead of behind whole
        generations."""
        sched = self._decode_sched
        if sched is None or not sched.live:
            return 0
        if self._decode_should_yield():
            self._decode_yield_streak += 1
            self._preempt_counter.inc()
            return 0
        self._decode_yield_streak = 0
        return self._finish_decode(client, sched.step())

    def _finish_decode(self, client: BrokerClient, finished) -> int:
        """Flush retired sequences: postprocess + typed result + held-back
        ack, end-to-end latency on the record's own lane series. Pages
        are already back in the pool (the scheduler freed them at
        retirement)."""
        if not finished:
            return 0
        cmds: list = []
        acks: list = []
        lanes_meta = []
        t1 = time.perf_counter()
        for seq in finished:
            info = self._gen_live.pop(seq, None)
            if info is None:
                continue
            uri, ack, m, lane, gen = info
            if gen != self._conn_gen:
                # admitted before a broker reconnect: the entry id means
                # nothing to the new connection — the record re-delivers
                # via its lease and is deduped by result idempotence
                continue
            try:
                pred = seq.result
                if self.postprocess is not None:
                    pred = self.postprocess(pred)
                val = schema.encode_result(pred, self.cipher)
            except Exception as e:
                logger.warning("postprocess failed for %s: %s", uri, e)
                val = schema.encode_error(
                    f"postprocess failed: {e}", self.cipher)
            cmds.append(("HSET", self.result_key, uri, val))
            acks.append(ack)
            lanes_meta.append((m, lane, uri, seq))
        if not acks and not cmds:
            return 0
        n = len(acks)
        with self._state_lock:
            self.records_out += n
        self._rec_counter.inc(n)
        for m, lane, uri, seq in lanes_meta:
            # trace-sampled sequences stamp their uri as the exemplar —
            # the same id the scheduler recorded decode_step spans under
            ex = uri if seq.trace_uri is not None else None
            if m is not None:
                self._latency_hist.get(
                    lane, self._latency_hist[schema.DEFAULT_PRIORITY]
                ).observe(max(0.0, t1 - m[0]), exemplar=ex)
            # cost settlement: the scheduler accumulated this sequence's
            # share of every wide step it rode and its page high water
            lane_key = lane if lane in self._cost_steps_hist \
                else schema.DEFAULT_PRIORITY
            self._cost_device_hist[(lane_key, "generate")].observe(
                max(0.0, seq.device_s), exemplar=ex)
            self._cost_steps_hist[lane_key].observe(seq.generated)
            self._cost_pages_hist[lane_key].observe(seq.pages_held)
        client.pipeline(cmds + acks)
        self._mark_done(acks, self._conn_gen)
        return n

    def _abort_decode(self):
        """Broker reconnect / shutdown: drop every live sequence — pages
        free immediately, held-back acks are discarded, and the un-acked
        entries re-deliver via their lease (at-least-once, never a
        double ack)."""
        if self._decode_sched is not None and self._decode_sched.live:
            self._decode_sched.abort_all()
        self._gen_live.clear()
        self._decode_yield_streak = 0

    # ----------------------------------------------------------- failover
    @property
    def failover_active(self) -> bool:
        """True while dispatch is swapped onto the CPU fallback rungs —
        /healthz reports degraded-but-serving (never 503) in this mode."""
        with self._state_lock:
            return self._failover

    def _enter_failover(self, err):
        with self._state_lock:
            if self._failover:
                return
            self._failover = True
            self._failover_t0 = time.perf_counter()
        logger.warning("backend loss (%s); draining onto the CPU "
                       "fallback rungs", err)
        if self._supervisor is not None:
            self._supervisor.report_failure(err)

    def _exit_failover(self):
        with self._state_lock:
            if not self._failover:
                return
            self._failover = False
            self._failover_t0 = None
        logger.warning("backend recovered; dispatch swapped back to the "
                       "accelerator rungs")

    def _failover_redispatch(self, client: BrokerClient,
                             comp: Completed) -> Optional[int]:
        """Re-run one backend-lost batch through the pre-built CPU
        executable and flush its real results — the drain half of
        failover. Returns the flushed record count, or None when this
        batch cannot fail over (no CPU predict on the model, a ctx that
        predates the wiring, or the CPU path failing too) — the caller
        then falls through to the normal error-result path."""
        x = comp.ctx[6] if len(comp.ctx) > 6 else None
        cpu_predict = getattr(self.model, "predict_cpu", None)
        if x is None or cpu_predict is None or isinstance(x, _GenBatch):
            # a generate batch has no one-shot CPU rung to fail over to —
            # its records take the normal error-result path
            return None
        self._enter_failover(comp.error)
        try:
            preds = np.asarray(cpu_predict(x))
        except Exception:
            logger.exception("CPU failover redispatch failed; falling "
                             "back to error results")
            return None
        with self._state_lock:
            t0, self._failover_t0 = self._failover_t0, None
        if t0 is not None:
            # drain → first CPU result
            dt = time.perf_counter() - t0
            with self._state_lock:
                self.failover_seconds.append(dt)
            self.timer.record("failover", dt)
        return self._finish(client, comp._replace(result=preds, error=None))

    def _finish(self, client: BrokerClient, comp: Completed) -> int:
        """Drain stage: postprocess + result/ack flush for one retired
        batch. A batch lost to the *backend* (not a model bug) first gets
        one shot at the CPU failover path — only when that is off or also
        fails do its records get error results."""
        if comp.error is not None and self._cpu_fallback \
                and resilience.is_backend_loss(comp.error):
            served = self._failover_redispatch(client, comp)
            if served is not None:
                return served
        uris, err_cmds, ack_cmds, n, trace, metas = comp.ctx[:6]
        gen = comp.ctx[7] if len(comp.ctx) > 7 else self._conn_gen
        # err_cmds are already counted where they were created (_produce):
        # expired results ride the same flush but belong to the expired
        # counter, never the error counter
        if comp.error is not None:
            # model incompatibility: every record gets an error result and
            # the entries are acked — losing them silently would hang the
            # clients AND pin the broker's GC low-water mark forever
            logger.error("inference failed for batch of %d: %s",
                         n, comp.error)
            err = schema.encode_error(f"inference failed: {comp.error}",
                                      self.cipher)
            client.pipeline(
                err_cmds
                + [("HSET", self.result_key, uri, err) for uri in uris]
                + ack_cmds)
            self._mark_done(ack_cmds, gen)
            self.timer.record("inference_error", comp.inflight_s)
            self._err_counter.inc(n)
            return 0
        self.timer.record("inference", comp.inflight_s)
        preds = np.asarray(comp.result)[:n]
        t0 = time.perf_counter()
        cmds = list(err_cmds)
        for uri, pred in zip(uris, preds):
            # a postprocess/encode failure on ONE record must not discard
            # the whole batch's results and acks (the batch would XCLAIM-
            # redeliver and fail deterministically forever)
            try:
                if self.postprocess is not None:
                    pred = self.postprocess(pred)
                val = schema.encode_result(pred, self.cipher)
            except Exception as e:
                logger.warning("postprocess failed for %s: %s", uri, e)
                val = schema.encode_error(
                    f"postprocess failed: {e}", self.cipher)
            cmds.append(("HSET", self.result_key, uri, val))
        # count BEFORE the flush: the broker makes the HSETs visible to
        # polling clients before it answers the pipelined write, so a
        # client that sees its result and immediately reads /metrics must
        # find the batch already counted
        t_pp_end = time.perf_counter()
        self.timer.record("postprocess", t_pp_end - t0)
        with self._state_lock:
            self.records_out += n
        self._rec_counter.inc(n)
        # end-to-end latency per stamped record: client enqueue (mapped
        # onto this clock by _queue_wait) → results about to flush, on
        # the record's own priority series. Sampled batches stamp the
        # record uri as the latency exemplar — the /trace link for this
        # very observation. Cost settlement: each record is billed an
        # equal share of the batch's device time.
        dev_share = max(0.0, comp.inflight_s) / max(1, n)
        for (m, lane), uri in zip(metas, uris):
            ex = uri if trace is not None else None
            if m is not None:
                self._latency_hist.get(
                    lane, self._latency_hist[schema.DEFAULT_PRIORITY]
                ).observe(max(0.0, t_pp_end - m[0]), exemplar=ex)
            self._cost_device_hist.get(
                (lane, "encode"),
                self._cost_device_hist[(schema.DEFAULT_PRIORITY, "encode")]
            ).observe(dev_share, exemplar=ex)
        if trace is not None:
            self._record_batch_trace(uris, trace, comp, t0, t_pp_end,
                                     metas)
        client.pipeline(cmds + ack_cmds)
        self._mark_done(ack_cmds, gen)
        return n

    def _record_batch_trace(self, uris, trace, comp: Completed,
                            t_post0: float, t_post1: float, metas=()):
        """Turn the sampled batch's stage stamps into per-uri spans. The
        record's uri is the trace id, so ``observability.trace(uri)`` (or a
        frontend caller that kept its uri) gets the full decomposition:
        ``serve`` (root, dequeue start → postprocess end) over contiguous
        ``dequeue``/``preprocess``/``device``/``postprocess`` children,
        with ``dispatch`` a sub-span of ``device``. Batch-level stages are
        shared verbatim by every uri in the batch. Records that carried a
        client stamp additionally get the measured ``queue_wait`` span
        (enqueue → dequeue-return) ahead of the engine stages — parentless
        like ``client_enqueue``, because both cross the process boundary."""
        t_dq0, t_dq1, t_pp0, t_pp1 = trace
        tr = self._tracer
        for uri, ml in zip(uris, list(metas) or [None] * len(uris)):
            m = ml[0] if ml else None
            if m is not None:
                tr.record(uri, "queue_wait", m[0], t_dq1)
            tr.record(uri, "dequeue", t_dq0, t_dq1, parent="serve")
            tr.record(uri, "preprocess", t_pp0, t_pp1, parent="serve")
            tr.record(uri, "dispatch", comp.t_submit,
                      comp.t_submit + comp.dispatch_s, parent="device")
            tr.record(uri, "device", comp.t_submit,
                      comp.t_submit + comp.inflight_s, parent="serve")
            tr.record(uri, "postprocess", t_post0, t_post1, parent="serve")
            tr.record(uri, "serve", t_dq0, t_post1)

    def _serve_once(self, client: BrokerClient,
                    pipe: Optional[DevicePipeline] = None) -> int:
        """One loop turn: produce a batch and stage its dispatch; retire
        batches the window pushed out (or everything, when the stream
        idles — a lone request must not wait for the window to fill)."""
        self._admission_tick(client)
        if pipe is None:                         # direct-call compatibility
            pipe = self._make_pipe()
            done = []
            produced = self._produce(client, self.block_ms)
            if produced is not None:
                done = pipe.submit(*produced)
            done += pipe.drain()
            return (sum(self._finish(client, c) for c in done)
                    + self._decode_tick(client))
        # while batches are in flight — or the decode scheduler holds
        # live sequences — poll instead of blocking in the broker read:
        # there is work ready to advance right now
        decode_live = (self._decode_sched is not None
                       and self._decode_sched.live > 0)
        block_ms = 0 if (pipe.in_flight or decode_live) else self.block_ms
        produced = self._produce(client, block_ms)
        if produced is not None:
            done = pipe.submit(*produced)
            if self.pipeline_window == 0:        # measured sync baseline
                done += pipe.drain()
        else:
            done = pipe.drain()
        served = sum(self._finish(client, c) for c in done)
        # decode advances AFTER the encode work of this turn was staged:
        # one wide step per turn, preempted when a waiting encode lane
        # outranks the decoding lanes
        return served + self._decode_tick(client)

    # ------------------------------------------------- admission control
    def _admission_tick(self, client: BrokerClient):
        """Periodic (``ZOO_SERVING_ADMISSION_S``) control step on the
        serve thread: when any per-lane p99 burn is past the shed
        threshold (the per-priority SLOs in common/slo.py — ``shed=False``
        there, so they drive admission, never the /healthz 503), flip the
        broker's batch-lane XSHED flag so NEW batch enqueues fast-fail at
        XADD while interactive keeps flowing; un-flip once the burn
        clears. The per-lane queue-depth gauges refresh on the same
        cadence."""
        if self._admission_interval_s <= 0:
            return
        now = time.perf_counter()
        if now - self._last_admission < self._admission_interval_s:
            return
        self._last_admission = now
        mon = slo.get_monitor()
        try:
            mon.tick_if_stale()
        except Exception:
            logger.debug("slo sample failed", exc_info=True)
        want = any(mon.burning(f"serving_p99_latency_{lane}")
                   for lane in schema.PRIORITIES)
        with self._state_lock:
            flip = want != self.admission_shedding or self._admission_dirty
        if flip:
            # dirty forces a re-assert after a reconnect: a RESTARTED
            # broker lost its shed flags
            client.xshed_set(self.stream, self.ADMISSION_LANE, want)
            with self._state_lock:
                self.admission_shedding = want
                self._admission_dirty = False
            self._admission_gauge.set(1.0 if want else 0.0)
            logger.warning("admission control: %s lane %s",
                           self.ADMISSION_LANE,
                           "SHEDDING" if want else "accepting")
        for lane in schema.PRIORITIES:
            self._lane_depth_gauge[lane].set(
                client.xlen(self.stream, lane))

    def _make_pipe(self) -> DevicePipeline:
        return DevicePipeline(self._dispatch,
                              window=max(1, self.pipeline_window),
                              fetch_fn=self._fetch, timer=self.timer)

    def _run(self):
        logger.info("serving started: stream=%s batch=%d window=%d",
                    self.stream, self.batch_size, self.pipeline_window)
        client: Optional[BrokerClient] = None
        # the pipeline outlives broker reconnects: in-flight device work is
        # finished against the redialed client, so results are never lost
        # to a socket failure between dispatch and drain
        pipe = self._make_pipe()
        while not self._stop.is_set():
            try:
                if client is None:
                    client = BrokerClient(host=self.broker_host,
                                          port=self.broker_port)
                if self._warmup_enabled and not self._warm_kicked:
                    # the model had no input spec at start() (nothing
                    # loaded yet) — kick the ladder warmup the moment it
                    # can describe its shapes
                    self._kick_warmup()
                if self._supervisor is not None and self.failover_active \
                        and self._supervisor.state == \
                        resilience.BackendSupervisor.OK:
                    # the supervisor's probe streak says the backend is
                    # back: swap dispatch off the CPU rungs
                    self._exit_failover()
                self._serve_once(client, pipe)
            except (ConnectionError, OSError):
                # broker died or the socket went bad: DROP the client and
                # redial next round (keeping a dead client would loop
                # forever on bad-fd errors)
                if self._stop.is_set():
                    break
                logger.warning("broker connection lost; reconnecting")
                if client is not None:
                    client.close()
                    client = None
                # a restarted broker reuses entry ids from 1: the dedupe
                # ring and claim backlog describe a dead connection
                self._conn_gen += 1
                self._seen_client_gen = 0   # fresh client starts at gen 0
                self._inflight_ids.clear()
                self._done_ids.clear()
                self._claim_backlog.clear()
                self._asm.clear()
                self._abort_decode()
                with self._state_lock:
                    # re-assert the shed flag on the next admission tick —
                    # a restarted broker came up accepting everything
                    self._admission_dirty = True
                time.sleep(0.2)
            except Exception:
                # the loop is the service — survive anything per-batch
                logger.exception("serve step failed; continuing")
                time.sleep(0.05)
        # drain-on-stop: in-flight batches still flush their results/acks
        # so a clean shutdown never strands dispatched work
        try:
            for c in pipe.drain():
                if client is not None:
                    self._finish(client, c)
        except Exception:
            logger.exception("final drain failed; pending entries will be "
                             "re-delivered via XCLAIM")
        # live decode sequences don't run to completion on stop: their
        # entries were never acked, so another replica (or a restart)
        # re-serves them from the lease — bounded shutdown wins
        self._abort_decode()
        if client is not None:
            client.close()

    # -------------------------------------------------------------- fleet
    def set_advertise(self, host: str, port: int):
        """Where peers can scrape this replica's ``/metrics`` — filled in
        by the FrontEnd that owns this engine (port 0 = headless)."""
        with self._state_lock:   # heartbeater reads it from its thread
            self._advertise = (host, int(port))

    def _replica_info(self) -> fleet.ReplicaInfo:
        with self._state_lock:
            n = self.records_out
            host, port = self._advertise
            started = self._started_wall
        # wall clock by design: heartbeat ages are compared across
        # processes/hosts (see common/fleet.py module docstring)
        now = time.time()  # zoolint: disable=wallclock-hotpath
        return fleet.ReplicaInfo(
            replica_id=self.replica_id, host=host, port=port,
            started_at=started, last_heartbeat=now,
            records_total=n, stream=self.stream)

    # ---------------------------------------------------------------- api
    def start(self) -> "ClusterServing":
        if self._thread is not None:
            return self
        # ZOO_FLIGHT_RECORDER=1: ring-buffer the serve-loop spans and dump
        # a postmortem to zoo_tpu_logs/ on SIGTERM — a killed serving
        # replica leaves evidence of what its pipeline was doing
        from analytics_zoo_tpu.common import profiling
        profiling.maybe_arm_from_env()
        # retain windowed metric history while serving (ISSUE 17): the
        # background sampler feeds /metrics/history, /query and the SLO
        # monitor's burn windows (idempotent; ZOO_TS_TICK_S=0 opts out)
        timeseries.get_store().start()
        # supervise the backend only when failover can act on its verdicts
        # (or a fault drill wants to observe them) — plain deployments get
        # no extra thread
        if self._cpu_fallback or resilience.fault_plan_active():
            sup = resilience.get_supervisor()
            with self._state_lock:
                self._supervisor = sup
            sup.ensure_started()
        if self._warmup_enabled:
            # background AOT over the whole ladder: the serve thread then
            # swaps buckets without ever compiling
            self._kick_warmup()
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        # join the fleet: periodic heartbeats through the broker hash so
        # any frontend can enumerate/scrape this replica
        # (ZOO_FLEET_HEARTBEAT_S=0 opts out)
        if self._heartbeater is None and fleet.heartbeat_interval_s() > 0:
            with self._state_lock:
                self._started_wall = \
                    time.time()  # zoolint: disable=wallclock-hotpath
            registry = fleet.ReplicaRegistry(self.broker_host,
                                             self.broker_port)
            self._heartbeater = fleet.Heartbeater(registry,
                                                  self._replica_info)
            self._heartbeater.start()
            # watch the fleet for crashed peers: on orphaned pending
            # entries the supervisor expedites this replica's next reclaim
            # sweep instead of waiting out the rate limiter
            self._replica_supervisor = fleet.ReplicaSupervisor(
                registry, self.stream, self.group,
                broker_host=self.broker_host, broker_port=self.broker_port,
                own_replica_id=self.replica_id,
                on_orphans=self._expedite_reclaim)
            self._replica_supervisor.start()
        return self

    def _expedite_reclaim(self, n_orphans: int):
        """ReplicaSupervisor callback: a stale peer left ``n_orphans``
        unacked entries — run the next reclaim sweep immediately (the
        entries still wait out their lease inside the broker)."""
        self._reclaim_asap.set()

    def stop(self):
        """Graceful drain: stop reading → flush in-flight → ack →
        deregister. The serve thread joins BEFORE the heartbeater
        deregisters — deregistering first would mark this replica's
        pending entries orphaned while the final drain is still about to
        ack them, handing peers a double-processing window."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        rsup, self._replica_supervisor = self._replica_supervisor, None
        if rsup is not None:
            rsup.stop()
        hb, self._heartbeater = self._heartbeater, None
        if hb is not None:
            hb.stop()   # deregisters only now, after the final drain acked
        # the supervisor is a process singleton, but the engine is the
        # process's deployment unit — stop the probe loop with the serving
        with self._state_lock:
            sup, self._supervisor = self._supervisor, None
        if sup is not None:
            sup.stop()

    def decode_state(self) -> Dict:
        """Decode occupancy at a glance — the /healthz ``decode`` block:
        live sequences, page-pool pages in use/free, preemptions since
        start. Counts are read without the serve thread's cooperation
        (int/len reads of scheduler state — point-in-time, never exact
        mid-step), which is the health endpoint's contract everywhere."""
        sched = self._decode_sched
        out = {"live_sequences": int(sched.live) if sched else 0,
               "steps_run": int(sched.steps_run) if sched else 0,
               "preemptions": int(self._preempt_counter.value),
               "pages_in_use": 0, "pages_free": 0}
        alloc = sched.allocator if sched else None
        if alloc is not None:
            out["pages_in_use"] = int(alloc.n_in_use)
            out["pages_free"] = int(alloc.n_free)
        return out

    def metrics(self) -> Dict:
        """Throughput + stage latencies (ref Flink numRecordsOutPerSecond +
        Timer stats)."""
        with self._state_lock:
            out = {"records_out": self.records_out,
                   "records_redelivered": self.records_redelivered,
                   "lease_reclaims": self.lease_reclaims,
                   "records_expired": self.records_expired,
                   "admission_shedding": self.admission_shedding}
        out.update(self.timer.summary())
        # model-parallel placement: strategy, shard count and per-shard
        # HBM bytes when the model was sharded (InferenceModel.shard)
        fn = getattr(self.model, "shard_info", None)
        if fn is not None:
            try:
                info = fn()
            except Exception:
                info = None
            if info:
                out["sharding"] = info
        return out

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
