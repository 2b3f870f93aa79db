"""Profiling & flight recorder — the diagnostic layer over telemetry
(ISSUE 3 tentpole).

PR 2's telemetry collects spans and counters but cannot answer the round-5
perf questions: *where* did a step's time go, what MFU is the chip actually
sustaining, how much HBM is resident, and what was happening when a run
wedged. This module adds the four missing pieces:

- **Chrome-trace export** — serialize the process Tracer's span store to
  Chrome Trace Event JSON (loadable in Perfetto / ``chrome://tracing``):
  :func:`chrome_trace`, :func:`dump_trace`, served by the FrontEnd's
  ``GET /trace``. One track (tid) per trace id, so a serving record's
  dequeue/preprocess/device/postprocess stages and a training step's
  data-wait/dispatch/device/callback phases each render as one row.
- **StepProfiler** — training decomposition used by
  ``JaxEstimator.fit``, on the host's clock and never with a fence of its
  own: publishes ``zoo_step_flops`` (XLA ``cost_analysis()`` of the
  ahead-of-time executable), ``zoo_mfu`` (steps x flops / a flush window's
  seconds / chip peak), ``zoo_hbm_bytes`` (``device.memory_stats()`` with
  a live-array-bytes fallback for backends that expose none, e.g. CPU), a
  ``zoo_train_phase_seconds`` histogram, and sampled step traces.
- **scope index** — :func:`scope_index`: for an executable the program
  compiled ahead of time, which named scope (flax module path,
  ``optimizer``, ``loss`` ...) and which phase each instruction of the
  optimized HLO belongs to. A device trace names its op events by
  instruction; the index turns them into time by part of the model.
  Beside it :func:`step_counts`: how many values the program holds behind
  an optimization barrier (``ops/hold.py``) and how many times the
  compiled program evaluates an ``erfc`` and generates a dropout mask.
- **FlightRecorder** — bounded ring buffer of recent spans + notes that
  dumps a postmortem JSON (spans, metrics snapshot, env, backend state)
  to ``zoo_tpu_logs/`` on SIGTERM or on demand. Arm with
  ``ZOO_FLIGHT_RECORDER=1``.
- **backend probe** — :func:`backend_state`, a non-blocking (daemon thread
  + join timeout) JAX backend/device-count probe, so ``GET /healthz`` can
  report a wedged or CPU-fallback backend without ever hanging the probe.

Everything degrades gracefully: no jax → ``jax-not-imported``; no
``memory_stats`` → live-array bytes; unknown chip → no MFU (never a
made-up constant). The peak-FLOPs table ``zoo_mfu`` divides by lives here.
"""

from __future__ import annotations

import base64
import binascii
import json
import logging
import os
import re
import signal
import sys
import threading
import weakref
from collections import deque
from contextlib import contextmanager
from time import perf_counter
from typing import Any, Dict, Iterable, List, Optional, Tuple

from analytics_zoo_tpu.common import telemetry
from analytics_zoo_tpu.common.telemetry import Span

__all__ = [
    "PEAK_FLOPS", "device_peak_flops", "hbm_bytes",
    "chrome_trace", "chrome_trace_events", "dump_trace", "StepProfiler",
    "FlightRecorder", "get_flight_recorder", "maybe_arm_from_env",
    "backend_state", "DUMP_DIR", "reset_for_tests",
    "note_executable", "scope_index", "parse_scope_index",
    "step_counts", "count_elementwise_evals", "count_kernel_calls",
    "count_flash_grid_steps", "count_flash_score_pairs",
    "count_flash_layouts",
]

logger = logging.getLogger(__name__)

# ``<checkout>/zoo_tpu_logs``: where flight-recorder postmortems go
# (``ZOO_FLIGHT_RECORDER_DIR`` overrides), and the anchor for the compile
# cache and the autotune verdicts. Resolved from this file's location, not
# the cwd: the directory is part of the compile cache's key, so a path
# that moves with the caller never hits.
DUMP_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "zoo_tpu_logs")

# peak dense-matmul FLOP/s per chip (bf16), keyed by device_kind: what
# ``zoo_mfu`` divides by. A new chip is a row here; the environment cannot
# replace one. tests/test_profiling.py holds the rows the benchmark's own
# table (benchmarks/peaks.json) names equal to it.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops(device=None) -> Optional[float]:
    """Peak FLOP/s for ``device`` (default: first visible device), from
    ``PEAK_FLOPS`` by its ``device_kind``. ``None`` for unknown chips (CPU
    backend): MFU is then not published — never derived from a made-up
    constant."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        return PEAK_FLOPS.get(device.device_kind)
    except Exception:
        return None


def hbm_bytes(device=None) -> Tuple[Optional[int], str]:
    """(resident device bytes, source). Source is ``memory_stats`` when
    the backend reports ``bytes_in_use`` (real TPU/GPU HBM accounting) or
    ``live_arrays`` — the summed ``nbytes`` of every live ``jax.Array`` —
    on backends like CPU where ``memory_stats()`` is ``None``."""
    try:
        import jax
        if device is None:
            device = jax.devices()[0]
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"]), "memory_stats"
        return (sum(int(getattr(a, "nbytes", 0))
                    for a in jax.live_arrays()), "live_arrays")
    except Exception:
        return None, "unavailable"


# -------------------------------------------------------- chrome trace

def chrome_trace_events(
        traces: Optional[Dict[str, List[Span]]] = None,
        tracer: Optional[telemetry.Tracer] = None) -> List[dict]:
    """Flatten a span store into Chrome Trace Event dicts.

    Complete ("ph":"X") events, timestamps in µs relative to the earliest
    span so the trace opens at t=0; one tid per trace id with a
    ``thread_name`` metadata event, so every trace renders as its own
    labeled row in Perfetto."""
    if traces is None:
        traces = (tracer or telemetry.get_tracer()).traces()
    pid = os.getpid()
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "analytics_zoo_tpu"}}]
    all_spans = [s for spans in traces.values() for s in spans]
    t0 = min((s.start for s in all_spans), default=0.0)
    for tid, (trace_id, spans) in enumerate(traces.items(), start=1):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": trace_id}})
        for s in sorted(spans, key=lambda s: s.start):
            events.append({
                "name": s.name, "cat": "zoo", "ph": "X",
                "ts": round((s.start - t0) * 1e6, 3),
                "dur": round(s.duration * 1e6, 3),
                "pid": pid, "tid": tid,
                "args": {"trace_id": trace_id,
                         "parent": s.parent or ""}})
    return events


def chrome_trace(trace_id: Optional[str] = None,
                 tracer: Optional[telemetry.Tracer] = None) -> dict:
    """The tracer's span store as a Chrome Trace Event JSON object
    (optionally restricted to one ``trace_id``)."""
    tracer = tracer or telemetry.get_tracer()
    traces = tracer.traces()
    if trace_id is not None:
        traces = {k: v for k, v in traces.items() if k == trace_id}
    return {"displayTimeUnit": "ms",
            "traceEvents": chrome_trace_events(traces)}


def dump_trace(path: str, trace_id: Optional[str] = None,
               tracer: Optional[telemetry.Tracer] = None) -> str:
    """Write :func:`chrome_trace` to ``path``; returns the path."""
    obj = chrome_trace(trace_id, tracer=tracer)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)
    return path


# -------------------------------------------------------- step profiler

class StepProfiler:
    """Training decomposition for ``JaxEstimator.fit``, one per
    estimator. Everything is timed on the host around calls the loop
    makes anyway; nothing here waits for the device.

    - ``zoo_train_phase_seconds{phase=...}``: per step ``data_wait``,
      ``dispatch`` and ``callback`` (:meth:`observe_step`); per epoch-level
      interval ``prepare``, ``first_batch`` and ``flush``
      (:meth:`phase`, which also opens the tracer span and with it the
      ``zoo:`` annotation on the profiler's clock); per flush window
      ``device``, the window's seconds per optimizer step
      (:meth:`observe_window`).
    - ``zoo_step_flops`` — FLOPs of one optimizer step by XLA's
      ``cost_analysis()`` of the ahead-of-time executable
      (:meth:`set_flops`); ``zoo_mfu`` — steps x flops / the flush
      window's seconds / chip peak, refreshed at each flush, which is a
      host sync already; no peak or no flops → no MFU.
    - ``zoo_hbm_bytes{source=...}``, refreshed at each flush.
    - tracer spans under trace id ``{name}/step-{n}`` for every
      ``sample_every``-th step: ``step`` over contiguous ``data_wait`` /
      ``dispatch`` / ``callback`` children, chrome-trace exportable like
      the serving plane's stage traces.

    Metric objects are looked up in the current registry at each use, so
    a profiler outlives ``telemetry.reset_for_tests``."""

    def __init__(self, name: str = "train", sample_every: int = 10,
                 peak_flops: Optional[float] = None,
                 registry: Optional[telemetry.MetricsRegistry] = None,
                 tracer: Optional[telemetry.Tracer] = None):
        self._registry = registry
        self._tracer = tracer if tracer is not None else \
            telemetry.get_tracer()
        self.name = name
        self.sample_every = max(1, int(sample_every))
        self.peak_flops = (peak_flops if peak_flops is not None
                           else device_peak_flops())
        # written by the estimator's warm-up thread, read at each flush
        self._flops_lock = threading.Lock()
        self.flops: Optional[float] = None   # per optimizer step

    def _reg(self) -> telemetry.MetricsRegistry:
        return self._registry if self._registry is not None \
            else telemetry.get_registry()

    def _phase_hist(self, phase: str):
        return self._reg().histogram(
            "zoo_train_phase_seconds", "Training phase wall time on the "
            "host: per step, per epoch-level interval, per flush window",
            ("phase",)).labels(phase)

    # ------------------------------------------------------------ flops
    def set_flops(self, flops: Optional[float], per_steps: int = 1):
        """Record the compiled step's FLOP count (``per_steps`` optimizer
        steps per compiled call, e.g. a fused scan loop)."""
        if flops:
            per_step = float(flops) / max(1, int(per_steps))
            with self._flops_lock:
                self.flops = per_step
            self._reg().gauge(
                "zoo_step_flops", "FLOPs of one compiled optimizer step "
                "(XLA cost_analysis of the ahead-of-time executable)"
            ).set(per_step)

    def should_sample(self, step: int) -> bool:
        """Sampled steps get a ``{name}/step-{n}`` trace."""
        return step % self.sample_every == 0

    # ------------------------------------------------------------ steps
    def observe_step(self, step: int, t_start: float, data_wait_s: float,
                     dispatch_s: float, callback_s: float = 0.0):
        """One completed step (or fused loop of optimizer steps), phase
        durations measured by the caller; ``t_start`` is the
        ``perf_counter`` when the data wait began."""
        self._phase_hist("data_wait").observe(data_wait_s)
        self._phase_hist("dispatch").observe(dispatch_s)
        if callback_s:
            self._phase_hist("callback").observe(callback_s)
        if not self.should_sample(step):
            return
        # contiguous sub-spans reconstructed from the measured durations
        tid = f"{self.name}/step-{step}"
        t_disp = t_start + data_wait_s
        t_call = t_disp + dispatch_s
        end = t_call + callback_s
        self._tracer.record(tid, "step", t_start, end)
        self._tracer.record(tid, "data_wait", t_start, t_disp,
                            parent="step")
        self._tracer.record(tid, "dispatch", t_disp, t_call, parent="step")
        if callback_s:
            self._tracer.record(tid, "callback", t_call, end,
                                parent="step")

    @contextmanager
    def phase(self, span: str, label: str):
        """One epoch-level interval on every clock the program keeps: a
        tracer span called ``span`` (nested under the ambient one, and
        ``zoo:<span>`` in a profiler session) and one sample of
        ``zoo_train_phase_seconds{phase=label}``."""
        trace_id = self._tracer.current_trace_id() or self.name
        t0 = perf_counter()
        try:
            with self._tracer.span(span, trace_id=trace_id):
                yield
        finally:
            self._phase_hist(label).observe(perf_counter() - t0)

    def observe_window(self, n_steps: int, seconds: float):
        """A flush window that just ended in a host sync: ``n_steps``
        optimizer steps took ``seconds`` of wall time, data waits and all.
        Refreshes ``zoo_mfu``, ``zoo_hbm_bytes`` and the ``device`` phase
        (seconds per step over the window)."""
        if n_steps <= 0 or seconds <= 0:
            return
        self._phase_hist("device").observe(seconds / n_steps)
        if self.flops and self.peak_flops:
            self._reg().gauge(
                "zoo_mfu", "Model FLOPs utilization over the last flush "
                "window: steps x step flops / seconds / chip peak"
            ).set(self.flops * n_steps / seconds / self.peak_flops)
        n, src = hbm_bytes()
        if n is not None:
            self._reg().gauge("zoo_hbm_bytes", "Resident device memory",
                              ("source",)).labels(src).set(n)


# --------------------------------------------------------- scope index

class _Executable:
    """What the process keeps of one executable compiled ahead of time:
    enough to answer :func:`scope_index` after the executable and the
    estimator that built it are gone, and nothing that holds device
    memory."""

    __slots__ = ("fn", "sig", "hlo_text", "launches", "flops", "counts",
                 "index")

    def __init__(self, fn, sig, hlo_text, launches, flops, counts):
        self.fn, self.sig = fn, sig
        self.hlo_text, self.launches = hlo_text, launches
        self.flops, self.counts = flops, counts
        self.index: Optional[Dict[str, dict]] = None


_executables: Dict[str, _Executable] = {}
_executables_lock = threading.Lock()


def note_executable(name: str, exe, fn=None, sig=None,
                    lowered=None) -> Optional[float]:
    """Keep the optimized HLO text and XLA's FLOP count of ``exe``, just
    compiled under ``name`` (the newest executable of a name wins), count
    what :func:`step_counts` answers — the held values in the text of
    ``lowered``, the ``jax.stages.Lowered`` that ``exe`` was compiled
    from, the evaluations in the optimized text, the kernel launches found
    in it once (:func:`_kernel_launches`, kept for :func:`scope_index`) —
    and publish the counts as gauges; returns the FLOP count.
    ``ExecutableCache`` calls this
    after every build. Reading the text of a whole train step takes most
    of a second, so a rebuild of the same jitted ``fn`` for the same
    signature — every ``fit`` call warms its step again — keeps what is
    held. Never raises: a compile must not fail for tracing's sake."""
    with _executables_lock:
        held = _executables.get(name)
    if held is not None and fn is not None and held.fn is not None \
            and held.fn() is fn and held.sig == sig:
        _publish_counts(name, held.counts)
        return held.flops
    text = flops = launches = None
    counts: Dict[str, int] = {}
    try:
        text = exe.as_text()
        cost = exe.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0)) or None
        launches = _kernel_launches(text)
        counts = {**count_elementwise_evals(text),
                  **count_kernel_calls(launches),
                  **count_flash_grid_steps(launches),
                  **count_flash_score_pairs(launches),
                  **count_flash_layouts(launches)}
        if lowered is not None:
            counts["held_values"] = lowered.as_text().count(_BARRIER)
    except Exception:
        logger.debug("no HLO text or cost analysis for %s", name,
                     exc_info=True)
    try:
        ref = weakref.ref(fn) if fn is not None else None
    except TypeError:        # a callable that takes no weak reference
        ref = None
    rec = _Executable(ref, sig, text, launches, flops, counts)
    with _executables_lock:
        _executables[name] = rec
    _publish_counts(name, counts)
    return flops


#: the operation ``jax.lax.optimization_barrier`` lowers to. The compilers
#: expand it away at the end, so the optimized HLO shows no trace of it:
#: it is counted in the program as lowered
_BARRIER = "stablehlo.optimization_barrier"
#: XLA expands ``erfc`` into two polynomial branches around ONE ``exp``,
#: which keeps the primitive's ``op_name``
_ERFC_EVAL = re.compile(r'\sexponential\(.*op_name="[^"]*/erfc"')
#: ``jax.random.bernoulli`` ends in ONE comparison of the uniform draw
#: with the keep probability
_MASK_EVAL = re.compile(
    r'\scompare\(.*op_name="[^"]*jit\(_bernoulli\)/lt"')


def count_elementwise_evals(hlo_text: str) -> Dict[str, int]:
    """``{"erfc": n, "mask": n}``: how many times one run of the optimized
    HLO module evaluates the exact gelu's ``erfc`` and generates a dropout
    keep-mask, fused computations included — a producer the compiler fused
    into three products is counted three times, which is the point. (A
    loop's body counts once however often it runs.)"""
    return {"erfc": len(_ERFC_EVAL.findall(hlo_text)),
            "mask": len(_MASK_EVAL.findall(hlo_text))}


#: a pallas kernel in the TPU compiler's optimized HLO: a custom call whose
#: ``custom_call_config.body`` is the kernel's MLIR module as base64
#: bytecode. Neither the instruction's name nor its ``op_name``
#: (``.../pallas_call``) tells one kernel from another; the bytecode's
#: string table carries the kernel function's name in the clear. Ahead of
#: the body, among the call's frontend attributes, rides what the launch
#: gave ``pl.pallas_call`` as ``metadata`` (group 1; empty for a launch
#: that gave none)
_TPU_KERNEL_BODY = re.compile(
    r'custom_call_target="tpu_custom_call"'
    r'(?:.*?kernel_metadata=\{([^{}]*)\})?.*?"body":"([A-Za-z0-9+/=]*)"')
#: the TPU compiler's grouped matrix product (``jax.lax.ragged_dot``): a
#: kernel of the compiler's own, whose ``op_name`` is its bare name,
#: ``ragged-dot-none``; ``ragged-dot-metadata`` beside it only finds where
#: the groups start
_RAGGED_DOT = re.compile(r'op_name="ragged-dot-(?!metadata")')
#: the label the scope index gives the grouped matrix product's launches
RAGGED_DOT = "ragged_dot"
#: XLA prints a call's ``kernel_metadata``, where it is not empty, one key
#: a line: the one place where an instruction of the optimized text does
#: not end on the line it began
_KERNEL_METADATA = re.compile(r"kernel_metadata=\{[^{}]*\}")
#: label of ``zoo_step_kernel_calls`` → the kernel function: the flash
#: attention kernels (``ops/flash_attention.py``) ...
FLASH_KERNELS = {"flash_fwd": b"_flash_fwd_kernel",
                 "flash_bwd_dq": b"_flash_bwd_dq_kernel",
                 "flash_bwd_dkv": b"_flash_bwd_dkv_kernel"}
#: ... the q/k norm-and-rotary kernels (``ops/norm_rotary.py``) and the
#: expert layer's combine (``ops/moe_combine.py``: the window's rows
#: packed one to a tile, then each token's rows summed)
KERNEL_FUNCTIONS = {**FLASH_KERNELS,
                    "norm_rotary_fwd": b"_norm_rotary_fwd_kernel",
                    "norm_rotary_bwd": b"_norm_rotary_bwd_kernel",
                    "moe_pack_rows": b"_pack_rows_kernel",
                    "moe_sum_rows": b"_sum_rows_kernel"}
#: label ``kind`` of ``zoo_flash_grid_steps``: the kinds of tile a flash
#: kernel's launch lists (``flash_attention.TILE_KINDS``)
TILE_KINDS = ("interior", "diagonal", "dead")
#: a flash launch's two counts of score pairs (``flash_attention.tile_pairs``)
#: → label ``kind`` of ``zoo_flash_score_pairs``
SCORE_PAIRS = {"pairs": "computed", "allowed": "allowed"}


def _one_line_each(hlo_text: str) -> str:
    """``hlo_text`` with every instruction on one line."""
    return _KERNEL_METADATA.sub(
        lambda m: m.group(0).replace("\n", ""), hlo_text)


#: one launch of a known kernel in an optimized HLO text: its instruction's
#: name, its kernel's label and what it wrote into its call's
#: ``kernel_metadata`` (strings; empty where it wrote nothing)
Launch = Tuple[str, str, Dict[str, str]]


def _kernel_launches(hlo_text: str) -> List[Launch]:
    """Every custom call of a known kernel in the optimized HLO of a TPU
    executable, in the text's order: a pallas kernel of
    ``KERNEL_FUNCTIONS``, told by its function's name inside the call's
    body, and the compiler's grouped matrix product (``RAGGED_DOT``), told
    by its bare name."""
    launches = []
    for line in _one_line_each(hlo_text).splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            continue
        name = instruction.group(2)
        if _RAGGED_DOT.search(line):
            launches.append((name, RAGGED_DOT, {}))
            continue
        found = _TPU_KERNEL_BODY.search(line)
        try:
            module = base64.b64decode(found.group(2)) if found else b""
        except binascii.Error:
            continue
        for kernel, function in KERNEL_FUNCTIONS.items():
            if function in module:
                launches.append((name, kernel, dict(re.findall(
                    r'"(\w+)":"(\w*)"', found.group(1) or ""))))
                break
    return launches


def _launches_of(hlo) -> List[Launch]:
    """``hlo``, the launches :func:`_kernel_launches` found in an
    optimized HLO text, or that text, whose launches are then found."""
    return _kernel_launches(hlo) if isinstance(hlo, str) else hlo


def count_kernel_calls(launches) -> Dict[str, int]:
    """``{"flash_fwd": n, "flash_bwd_dq": n, "flash_bwd_dkv": n,
    "norm_rotary_fwd": n, "norm_rotary_bwd": n, "moe_pack_rows": n,
    "moe_sum_rows": n}``: the custom calls of each kernel in the optimized
    HLO of a TPU executable (``launches``: what :func:`_kernel_launches`
    found in it, or the text). A training step reads flash 1, 1, 1 per
    attention layer that takes the kernels; 2, 1, 1 where a
    ``jax.checkpoint`` around the layer does not keep
    ``flash_attention.RESIDUAL_NAMES`` and the backward pass launches the
    forward kernel again. Norm-and-rotary 4, 2 per rematerialised layer
    that runs it (q and k, forward and recomputed; their backward). The
    expert layer's combine 4, 4 per dropless layer that runs it: the
    first window's forward and backward, and the same two inside the
    second window's ``cond``, which a step seldom takes. All 0 off the
    TPU (the interpreter inlines a kernel) and where the work is XLA's."""
    counts = dict.fromkeys(KERNEL_FUNCTIONS, 0)
    for _, kernel, _ in _launches_of(launches):
        if kernel in counts:
            counts[kernel] += 1
    return counts


def _summed(launches, keys) -> Dict[str, int]:
    """``{"<kernel>/<key>": n}``: each of ``keys`` that a launch wrote as
    a number into its call's metadata, summed over a kernel's launches."""
    counts: Dict[str, int] = {}
    for _, kernel, metadata in _launches_of(launches):
        for key in keys:
            if metadata.get(key, "").isdigit():
                label = f"{kernel}/{key}"
                counts[label] = counts.get(label, 0) + int(metadata[key])
    return counts


def count_flash_grid_steps(launches) -> Dict[str, int]:
    """``{"<kernel>/<kind>": n}``: the grid steps the launches of each
    flash attention kernel in the optimized HLO of a TPU executable take
    (``launches`` as :func:`count_kernel_calls` takes them), by kind of
    tile (``TILE_KINDS``), as the launch listed them ahead of time
    (``flash_attention.tile_table``) and wrote them into its call's
    metadata; summed over the kernel's launches, all heads. A causal
    launch reads ``dead`` 0 unless it has query blocks that see no key at
    all. No key for a kernel the program does not launch: an executable
    without the kernels gives ``{}``."""
    return _summed(launches, TILE_KINDS)


def count_flash_score_pairs(launches) -> Dict[str, int]:
    """``{"<kernel>/pairs": n, "<kernel>/allowed": n}``: the score pairs
    the live tiles of each flash attention kernel's launches compute, and
    those of them that the mask and the true key length let through, as
    each launch counted them ahead of time (``flash_attention.tile_pairs``)
    and wrote them into its call's metadata; summed over the kernel's
    launches, all heads (``launches`` as :func:`count_kernel_calls` takes
    them). A launch that says neither (a program compiled before the keys
    were written) and an executable without the kernels give ``{}``."""
    return _summed(launches, SCORE_PAIRS)


def count_flash_layouts(launches) -> Dict[str, int]:
    """``{"<kernel>@<layout>,<kv>": n}``: the launches of each flash
    attention kernel in the optimized HLO of a TPU executable
    (``launches`` as :func:`count_kernel_calls` takes them) by how they
    find a head's blocks, as each launch wrote it into its call's
    metadata — ``layout`` ``rows`` (operands ``[batch, seq, heads·d]``
    as the projections write them, the head named by the index maps) or
    ``heads`` (lane-padded head-major copies); ``kv`` ``grouped`` (a
    key/value head read by the query heads of its group) or ``own`` (as
    many key/value heads as query heads; ``flash_attention._Operands``).
    A launch that says neither (a
    program compiled before the keys were written) is not counted; an
    executable without the kernels gives ``{}``."""
    counts: Dict[str, int] = {}
    for _, kernel, metadata in _launches_of(launches):
        layout, kv = metadata.get("layout"), metadata.get("kv")
        if layout and kv:
            key = f"{kernel}@{layout},{kv}"
            counts[key] = counts.get(key, 0) + 1
    return counts


def _publish_counts(name: str, counts: Dict[str, int]) -> None:
    reg = telemetry.get_registry()
    if "held_values" in counts:
        reg.gauge(
            "zoo_step_held_values", "Values the program as lowered holds "
            "behind an optimization barrier (ops/hold.py): one per exact "
            "gelu and per active dropout site of a training step, none in "
            "an inference program", ("executable",)
        ).labels(name).set(counts["held_values"])
    for kind in ("erfc", "mask"):
        if kind in counts:
            reg.gauge(
                "zoo_step_elementwise_evals", "Times the compiled program "
                "evaluates an erfc / generates a dropout mask; more than "
                "zoo_step_held_values accounts for means the compiler "
                "re-derives them inside their consumers",
                ("executable", "kind")).labels(name, kind).set(counts[kind])
    for kernel in KERNEL_FUNCTIONS:
        if kernel in counts:
            reg.gauge(
                "zoo_step_kernel_calls", "Custom calls of a pallas kernel "
                "in the compiled program; flash_fwd twice the backward "
                "kernels' count means a rematerialised layer launches the "
                "forward kernel again for its output and logsumexp",
                ("executable", "kernel")).labels(name, kernel).set(
                    counts[kernel])
    for key in counts:
        kernel, _, how = key.partition("@")
        if how:
            layout, _, kv = how.partition(",")
            reg.gauge(
                "zoo_flash_launches", "Launches of a flash attention "
                "kernel in the compiled program by how they find a head's "
                "blocks: layout rows (operands [batch, seq, heads*d], the "
                "head named by the index maps) or heads (lane-padded "
                "head-major copies); kv grouped (a key/value head read by "
                "its group's query heads) or own",
                ("executable", "kernel", "layout", "kv")).labels(
                    name, kernel, layout, kv).set(counts[key])
            continue
        kernel, _, kind = key.partition("/")
        if kind in SCORE_PAIRS:
            reg.gauge(
                "zoo_flash_score_pairs", "Score pairs the live tiles of a "
                "flash attention kernel's launches in the compiled program "
                "compute, and those of them the mask and the true key "
                "length allow: allowed over computed is the share of the "
                "kernel's score work that is wanted",
                ("executable", "kernel", "kind")).labels(
                    name, kernel, SCORE_PAIRS[kind]).set(counts[key])
        elif kind:
            reg.gauge(
                "zoo_flash_grid_steps", "Grid steps the launches of a "
                "flash attention kernel in the compiled program take, by "
                "kind of tile: interior (no mask), diagonal (masked), "
                "dead (no work: kept only where a block has no live tile)",
                ("executable", "kernel", "kind")).labels(
                    name, kernel, kind).set(counts[key])


def step_counts(name: str) -> Optional[Dict[str, int]]:
    """``{"held_values", "erfc", "mask", "flash_fwd", "flash_bwd_dq",
    "flash_bwd_dkv", "norm_rotary_fwd", "norm_rotary_bwd",
    "moe_pack_rows", "moe_sum_rows"}`` of the
    executable last compiled ahead of time under
    ``name``, and ``"<kernel>/<kind>"``, ``"<kernel>/pairs"``,
    ``"<kernel>/allowed"`` and ``"<kernel>@<layout>,<kv>"`` for each flash
    kernel it launches: the optimization barriers of the program as
    lowered (left out where the lowered program was not at hand),
    :func:`count_elementwise_evals`, :func:`count_kernel_calls`,
    :func:`count_flash_grid_steps`, :func:`count_flash_score_pairs` and
    :func:`count_flash_layouts` of its optimized HLO; ``None`` when
    nothing was compiled under that name. The same numbers are the gauges
    ``zoo_step_held_values{executable}``,
    ``zoo_step_elementwise_evals{executable,kind}``,
    ``zoo_step_kernel_calls{executable,kernel}``,
    ``zoo_flash_grid_steps{executable,kernel,kind}``,
    ``zoo_flash_score_pairs{executable,kernel,kind=computed|allowed}`` and
    ``zoo_flash_launches{executable,kernel,layout,kv}``."""
    with _executables_lock:
        rec = _executables.get(name)
    return dict(rec.counts) if rec is not None else None


def scope_index(name: str) -> Optional[Dict[str, dict]]:
    """``{instruction name: {"scope", "phase", "scopes", "opcode"}}``, and
    ``"kernel"`` (and ``"tiles"``) on a kernel launch's entry, for
    the executable last compiled ahead of time under ``name``
    (``"estimator_train_step"``, ``"estimator_train_scan"``, an inference
    model's cache name); ``None`` when nothing was. See
    :func:`parse_scope_index`. Parsed on first request."""
    with _executables_lock:
        rec = _executables.get(name)
    if rec is None or rec.hlo_text is None:
        return None
    if rec.index is None:
        rec.index = parse_scope_index(rec.hlo_text, rec.launches)
    return rec.index


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(")
_INSTRUCTION = re.compile(
    r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                     r"false_computation|calls)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_TRANSFORM = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
#: opcodes whose callee runs as ops of its own on the device
_CONTAINERS = frozenset({"while", "call", "conditional", "async-start"})
#: opcodes that take no device time and never show as an op event
_NO_TIME = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"})
_PRODUCTS = frozenset({"dot", "convolution"})


def _scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, phase) of one ``metadata.op_name``. The last component is
    the primitive's name and goes; ``jit(f)`` goes whole (a function's
    name is no scope); any other transform wrapper is peeled off its
    scope: ``jit(step_fn)/transpose(jvp(Classifier))/bert/block_3/
    attention/out/dot_general`` is ``Classifier/bert/block_3/attention/
    out``, backward."""
    transforms, scope = set(), []
    for part in op_name.split("/")[:-1]:
        while True:
            m = _TRANSFORM.match(part)
            if not m:
                break
            transforms.add(m.group(1))
            part = "" if m.group(1) in ("jit", "pjit") else m.group(2)
        if part:
            scope.append(part)
    if "transpose" in transforms:
        phase = "backward"
    elif "jvp" in transforms:
        phase = "forward"
    elif scope and scope[0] == "optimizer":
        phase = "optimizer"
    else:
        phase = "other"
    return "/".join(scope), phase


def parse_scope_index(hlo_text: str,
                      launches: Optional[List[Launch]] = None
                      ) -> Dict[str, dict]:
    """The scope index of one optimized HLO module's text: for every
    instruction the device runs as an op of its own — those of the entry
    computation and of the bodies of its ``while``/``call``/``conditional``
    instructions — ``{"scope", "phase", "scopes", "opcode"}``, and on the
    entry of a kernel's launch ``kernel``, its label (``launches``: what
    :func:`_kernel_launches` found in the text, found here where not
    given): one of ``KERNEL_FUNCTIONS``, or ``RAGGED_DOT`` for the
    compiler's grouped matrix product. A flash launch's entry carries
    ``tiles`` too: the grid steps its metadata lists, all kinds and all
    heads. Every other entry has neither key.

    ``scope`` and ``phase`` (``forward`` | ``backward`` | ``optimizer`` |
    ``other``) come from the instruction's ``metadata.op_name``
    (:func:`_scope_of`). A fusion takes the scope of the
    ``dot``/``convolution`` inside its fused computation if it has one,
    else of that computation's root, else its own; ``scopes`` lists every
    distinct scope found inside, so a weight-gradient product fused with
    the optimizer's update counts with its product and shows as mixed.
    An instruction the compiler left nameless (the copies and slices that
    bring a weight in ahead of its use, and the waits for them; an
    instruction whose ``op_name`` is the compiler's own bare name for
    what it put there, a kernel or a rewritten gather, with no name stack
    in it) takes
    ``scope`` and ``phase`` of the nearest named instruction that
    consumes its result (or, for a copy out of the step, produced its
    operand), with ``scopes`` left empty; ``scope`` is ``None`` only
    where neither exists; inside a called computation (a ``conditional``'s
    branch, a ``while``'s body) such an instruction falls back to the
    calling instruction's scope."""
    computations: Dict[str, list] = {}
    entry = current = None
    for line in _one_line_each(hlo_text).splitlines():
        if not line:
            continue
        if not line[0].isspace():
            current = None
            m = _COMPUTATION.match(line) if line.endswith("{") else None
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if not m:
            continue
        rest = line[m.end():]
        named = _OP_NAME.search(rest)
        opcode = m.group(3)
        callees = []
        if opcode == "fusion" or opcode in _CONTAINERS:
            callees = _CALLED.findall(rest)
            for group in _BRANCHES.findall(rest):
                callees += [c.strip().lstrip("%")
                            for c in group.split(",") if c.strip()]
        current.append((m.group(2), opcode,
                        named.group(1) if named else None,
                        bool(m.group(1)), callees, _OPERAND.findall(rest)))

    kernels = {name: (kernel, metadata)
               for name, kernel, metadata in _launches_of(
                   hlo_text if launches is None else launches)}
    index: Dict[str, dict] = {}
    seen = set()

    def visit(computation: str):
        if computation in seen:
            return
        seen.add(computation)
        body = computations.get(computation, ())
        for name, opcode, own, _, callees, _ in body:
            if opcode in _NO_TIME:
                continue
            names = [own] if own else []
            chosen = own
            if opcode == "fusion":
                inner = [i for c in callees for i in computations.get(c, ())]
                names += [i[2] for i in inner if i[2]]
                products = [i[2] for i in inner
                            if i[1] in _PRODUCTS and i[2]]
                roots = [i[2] for i in inner if i[3] and i[2]]
                chosen = (products or roots or [own])[0]
            scope, phase = _scope_of(chosen) if chosen else (None, "other")
            if opcode != "fusion":
                for callee in callees:
                    visit(callee)
                    # what a branch or a body runs that neither has a
                    # name nor a named neighbour inside it (the zeros of
                    # a branch that computes nothing, the copies around
                    # it) belongs to the instruction that calls it
                    for inner, *_ in computations.get(callee, ()):
                        entry = index.get(inner)
                        if entry and entry["scope"] is None and scope:
                            entry["scope"], entry["phase"] = scope, phase
            if chosen and "/" not in chosen:
                # what the compiler put in place of an instruction and
                # named itself (the TPU's grouped matrix product arrives
                # as "ragged-dot-none", a bit-packed gather as "gather"):
                # no name stack at all, so nameless like the copies below
                scope, phase, names = None, "other", []
            index[name] = {
                "scope": scope, "phase": phase, "opcode": opcode,
                "scopes": sorted({_scope_of(n)[0] for n in names})}
            if name in kernels:
                kernel, metadata = kernels[name]
                index[name]["kernel"] = kernel
                tiles = [int(metadata[kind]) for kind in TILE_KINDS
                         if metadata.get(kind, "").isdigit()]
                if tiles:
                    index[name]["tiles"] = sum(tiles)
        _inherit_from_neighbours(body, index)

    if entry is not None:
        visit(entry)
    return index


def _inherit_from_neighbours(body, index: Dict[str, dict]) -> None:
    """For one computation's instructions, already indexed: give each
    that the compiler left nameless the scope and phase of the nearest
    instruction with a scope that consumes its result — or, where none
    does (a copy of a result out of the step), that produced its operand
    — found breadth-first through instructions without one (program order
    breaks ties). A nameless ``custom-call`` looks among its operands'
    producers first."""
    users: Dict[str, list] = {}
    operands_of: Dict[str, list] = {}
    for name, _, _, _, _, operands in body:
        operands_of[name] = operands
        for operand in operands:
            users.setdefault(operand, []).append(name)

    def nearest(start: str, edges: Dict[str, list]):
        seen, frontier = {start}, [start]
        while frontier:
            reached = []
            for at in frontier:
                for other in edges.get(at, ()):
                    if index.get(other, {}).get("scopes"):
                        return index[other]
                    if other not in seen:
                        seen.add(other)
                        reached.append(other)
            frontier = reached
        return None

    for name, opcode, _, _, _, _ in body:
        entry = index.get(name)
        if entry is None or entry["scope"] is not None:
            continue
        # a kernel belongs with what feeds it (a weight-gradient product's
        # only user is the optimizer's update); a copy with what reads it
        first, then = (operands_of, users) if opcode == "custom-call" \
            else (users, operands_of)
        found = nearest(name, first) or nearest(name, then)
        if found is not None:
            entry["scope"], entry["phase"] = found["scope"], found["phase"]


# ----------------------------------------------------- flight recorder

class FlightRecorder:
    """Bounded ring of recent spans + free-form notes, dumpable as a
    postmortem JSON artifact.

    ``attach()`` hooks the process tracer so every recorded span (serving
    stages, pipeline dispatch windows, sampled training steps) lands in
    the ring; ``arm()`` installs a SIGTERM handler (chaining any previous
    one) so an external kill leaves an artifact; ``dump()`` writes the
    last N spans, a full metrics snapshot, selected env, and the backend
    probe state to ``zoo_tpu_logs/flightrec_*.json``."""

    _ENV_PREFIXES = ("ZOO_", "JAX_", "XLA_", "TPU_")

    def __init__(self, capacity: int = 256,
                 dump_dir: Optional[str] = None,
                 tracer: Optional[telemetry.Tracer] = None):
        self._tracer = tracer if tracer is not None else \
            telemetry.get_tracer()
        self._spans: "deque[Span]" = deque(maxlen=int(capacity))
        self._notes: "deque[str]" = deque(maxlen=64)
        self._lock = threading.Lock()
        self._attached = False
        self._prev_handlers: Dict[int, Any] = {}
        self._seq = 0
        # dump_once latch: trigger -> written path. The supervisor's
        # wedge dump and a later SIGTERM dump each own a trigger key, so
        # layered failure paths chain without double-writing an artifact.
        self._dumped: Dict[str, str] = {}
        # explicit dir wins; otherwise resolved at dump time so the env
        # override works even on a singleton created before it was set
        self.dump_dir = dump_dir

    # --------------------------------------------------------- feeding
    def observe(self, span: Span):
        self._spans.append(span)   # deque.append is atomic

    def note(self, msg: str):
        """Free-form breadcrumb (wedge notes, part names) for the dump."""
        self._notes.append(str(msg))

    def attach(self) -> "FlightRecorder":
        with self._lock:   # attach races detach on the teardown paths
            if not self._attached:
                self._tracer.add_hook(self.observe)
                self._attached = True
        return self

    def detach(self):
        with self._lock:
            if self._attached:
                self._tracer.remove_hook(self.observe)
                self._attached = False

    # --------------------------------------------------------- dumping
    def snapshot(self, reason: str = "") -> dict:
        spans = list(self._spans)
        env = {k: v for k, v in os.environ.items()
               if k.startswith(self._ENV_PREFIXES)}
        try:
            metrics = telemetry.snapshot()
        except Exception as e:
            metrics = {"error": repr(e)[:200]}
        return {
            "kind": "zoo_flight_recorder",
            "reason": reason,
            "pid": os.getpid(),
            "argv": list(sys.argv),
            "env": env,
            "backend": backend_state(),
            "notes": list(self._notes),
            "metrics": metrics,
            "spans": [{"trace_id": s.trace_id, "name": s.name,
                       "start": s.start, "end": s.end,
                       "duration_ms": round(s.duration * 1e3, 3),
                       "parent": s.parent} for s in spans],
        }

    def dump(self, reason: str = "", path: Optional[str] = None) -> str:
        """Write the postmortem; returns the path. Never raises — a
        failing dump on a dying process must not mask the original
        fault — returns "" on failure."""
        try:
            if path is None:
                with self._lock:
                    self._seq += 1
                    seq = self._seq
                import time
                stamp = int(time.time())   # zoolint: disable=wallclock-hotpath (dump filename)
                base = (self.dump_dir
                        or os.environ.get("ZOO_FLIGHT_RECORDER_DIR")
                        or DUMP_DIR)
                path = os.path.join(
                    base, f"flightrec_{stamp}_{os.getpid()}_{seq}.json")
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as fh:
                json.dump(self.snapshot(reason), fh)
            return path
        except Exception:
            return ""

    def dump_once(self, trigger: str, reason: str = "",
                  path: Optional[str] = None) -> str:
        """Write at most one postmortem per ``trigger`` key for the life
        of this recorder; repeat calls return the first call's path
        (possibly "" if that dump failed — failure latches too, so a
        dying process never retries dump I/O in a loop). This is how the
        supervisor's wedge dump and the SIGTERM handler layer without
        double-dumping."""
        with self._lock:
            if trigger in self._dumped:
                return self._dumped[trigger]
        out = self.dump(reason=reason or trigger, path=path)
        with self._lock:
            self._dumped.setdefault(trigger, out)
            return self._dumped[trigger]

    # --------------------------------------------------------- signals
    def _handler(self, signum, frame):
        self.dump_once(
            trigger=f"signal-{signal.Signals(signum).name}",
            reason=f"signal-{signal.Signals(signum).name}")
        prev = self._prev_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            # restore and re-deliver so the process still dies from
            # SIGTERM the way the sender expects, artifact written first
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    def arm(self, signals: Iterable[int] = (signal.SIGTERM,)) -> bool:
        """Install dump-on-signal handlers. Returns False (and installs
        nothing) off the main thread — CPython only allows signal
        handling there."""
        try:
            for sig in signals:
                prev = signal.signal(sig, self._handler)
                # never chain to ourselves: re-arming after a prior arm
                # would otherwise store self._handler as "previous" and
                # recurse (double-dump) on delivery
                if sig not in self._prev_handlers and \
                        prev is not self._handler:
                    self._prev_handlers[sig] = prev
        except ValueError:
            return False
        return True

    def disarm(self):
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev_handlers.clear()


_FLIGHT_RECORDER: Optional[FlightRecorder] = None
_FR_LOCK = threading.Lock()


def get_flight_recorder(capacity: int = 256) -> FlightRecorder:
    """Process-wide flight recorder, created and tracer-attached on first
    use."""
    global _FLIGHT_RECORDER
    with _FR_LOCK:
        if _FLIGHT_RECORDER is None:
            _FLIGHT_RECORDER = FlightRecorder(capacity=capacity)
        _FLIGHT_RECORDER.attach()
        return _FLIGHT_RECORDER


def maybe_arm_from_env() -> Optional[FlightRecorder]:
    """``ZOO_FLIGHT_RECORDER=1`` → attach + arm(SIGTERM) the singleton.
    Called from the serving engine's ``start()``."""
    if os.environ.get("ZOO_FLIGHT_RECORDER", "").lower() not in (
            "1", "true", "yes", "on"):
        return None
    fr = get_flight_recorder()
    fr.arm()
    return fr


# ------------------------------------------------------- backend probe

_BACKEND_CACHE: Dict[str, Any] = {}
# probe_backend is called from the serve loop, supervisors, and dump
# paths concurrently — the cache update must not interleave with clear()
_BACKEND_LOCK = threading.Lock()


def backend_state(timeout_s: float = 2.0) -> dict:
    """JAX backend/platform/device-count without ever blocking the
    caller: the probe runs in a daemon thread joined with a timeout, so a
    backend that does not answer yields ``{"status": "wedged"}`` instead
    of hanging a health endpoint. A successful probe is cached (the backend
    never changes within a process). If jax was never imported, reports
    that rather than triggering device init from a mere probe."""
    # fault-injection probe seam — checked before the success cache so a
    # planned `wedge@probe` drill works even on an already-probed process
    from analytics_zoo_tpu.common import resilience
    injected = resilience.probe_fault()
    if injected is not None:
        return {"status": "wedged", "injected": injected,
                "probe_timeout_s": timeout_s}
    if _BACKEND_CACHE.get("status") == "ok":
        return dict(_BACKEND_CACHE)
    if "jax" not in sys.modules:
        return {"status": "jax-not-imported"}
    result: Dict[str, Any] = {}

    def probe():
        try:
            import jax
            devs = jax.devices()
            result.update(status="ok", platform=devs[0].platform,
                          device_kind=devs[0].device_kind,
                          device_count=len(devs))
        except BaseException as e:
            result.update(status="error", error=repr(e)[:200])

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if not result:
        return {"status": "wedged", "probe_timeout_s": timeout_s}
    if result.get("status") == "ok":
        with _BACKEND_LOCK:
            _BACKEND_CACHE.update(result)
    return dict(result)


def reset_for_tests():
    """Called from telemetry.reset_for_tests(): drop the flight-recorder
    singleton (its tracer hook died with the trace clear), the backend
    probe cache and what is kept of compiled executables."""
    global _FLIGHT_RECORDER
    with _FR_LOCK:
        if _FLIGHT_RECORDER is not None:
            _FLIGHT_RECORDER.detach()
            _FLIGHT_RECORDER.disarm()
            _FLIGHT_RECORDER = None
    with _BACKEND_LOCK:
        _BACKEND_CACHE.clear()
    with _executables_lock:
        _executables.clear()
