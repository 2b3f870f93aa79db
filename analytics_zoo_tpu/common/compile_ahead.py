"""Compile-ahead execution — shape-bucket ladder, AOT executable cache,
and the persistent XLA compile cache (ISSUE 5 tentpole).

Every batch-shape change costs an XLA compile, and before this layer the
serving engine paid it *on the serve thread* exactly when backlog was
highest (``_grow_batch_on_backlog`` doubled the bucket in-band). The fix
is the same shape discipline the TPU serving literature converges on
(PAPERS.md: Gemma-on-TPU, Flare): a small fixed ladder of power-of-two
batch buckets, every incoming batch padded to its nearest rung, and every
rung's executable built ahead of time, off the hot path:

- **BucketLadder** — the bucket policy: power-of-two rungs between
  ``min_batch_size`` and ``max_batch_size`` (the top rung clamps to the
  max), ``rung_for(n)`` selection, ``up``/``down`` stepping.
- **ExecutableCache** — AOT-compiled executables keyed by the avals
  signature of the call, built via ``jitted.lower(*avals).compile()``
  either synchronously (a miss) or on a background warmup thread
  (``warm_async``). Warm lookups dispatch **directly through the stored
  executable**, never through ``jax.jit``'s call path — so the
  ``zoo_jit_cache_misses_total`` recompile counter stays flat by
  construction once the ladder is warm. Every compile is timed into
  ``zoo_compile_seconds`` and recorded as a ``compile`` span under the
  :data:`WARMUP_TRACE_ID` trace, which is how tests prove no serve-thread
  span ever overlaps a compile; its HLO text and FLOP count are kept for
  ``profiling.scope_index``.
- **configure_persistent_cache** — wires JAX's on-disk compilation cache
  (``JAX_COMPILATION_CACHE_DIR`` when set, else
  ``<checkout>/zoo_tpu_logs/xla_cache``) so process restarts skip cold
  compiles entirely: a background AOT compile in one process seeds the
  entry the next process's first jit call hits.

Import cost matches telemetry.py: stdlib + numpy only; jax is imported
lazily inside the functions that need it.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from time import perf_counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.common import profiling, resilience, telemetry

__all__ = [
    "BucketLadder", "ExecutableCache", "configure_persistent_cache",
    "pad_to_rung", "batch_avals", "tree_avals", "WARMUP_TRACE_ID",
    "register_warmup_thread", "draining",
]

logger = logging.getLogger(__name__)

#: trace id every compile span is recorded under — serve-thread spans are
#: keyed by record uri, so "no serve span overlaps a span of this trace"
#: is exactly the stall-free-warmup invariant
WARMUP_TRACE_ID = "compile_warmup"

#: persistent compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set — anchored at the checkout (see ``profiling.DUMP_DIR``)
DEFAULT_CACHE_DIR = os.path.join(profiling.DUMP_DIR, "xla_cache")

#: pad fraction is bounded [0, 1): the latency buckets make no sense here
_PAD_BUCKETS = (0.0, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.625, 0.75, 0.875,
                1.0)

_cache_lock = threading.Lock()
_cache_dir: Optional[str] = None
_cache_configured = False

# Warmup threads are daemons so they never block a healthy exit path by
# policy, but a daemon killed mid-XLA-compile takes the process down from
# C++ ("terminate called without an active exception"). The atexit drain
# cancels the remaining rungs and joins the in-flight compile, so a
# short-lived process (doc snippet, example script) exits cleanly even
# while a ladder is still warming.
_warm_threads_lock = threading.Lock()
_warm_threads: List[threading.Thread] = []
_draining = threading.Event()


def draining() -> bool:
    """True once interpreter shutdown began — warmup workers poll this
    between compiles and skip the rest of their rungs."""
    return _draining.is_set()


def register_warmup_thread(thread: threading.Thread) -> None:
    """Track a background warmup thread so process exit joins it instead
    of killing it inside an XLA compile."""
    with _warm_threads_lock:
        _warm_threads[:] = [t for t in _warm_threads if t.is_alive()]
        _warm_threads.append(thread)


def _drain_warmup_threads() -> None:
    _draining.set()
    with _warm_threads_lock:
        threads = list(_warm_threads)
    for t in threads:
        t.join()


atexit.register(_drain_warmup_threads)


def configure_persistent_cache() -> Optional[str]:
    """Make sure JAX's persistent compilation cache has a directory, so
    compiled executables survive process restarts (cold start skips
    straight to deserialization). Called from ``init_orca_context`` and
    the ``InferenceModel``/``ClusterServing`` constructors — before the
    process's first compile, so ``module.init`` and every jit after it is
    cached too. Idempotent and cheap after the first call.

    A directory placed from outside — ``JAX_COMPILATION_CACHE_DIR``, or
    ``jax_compilation_cache_dir`` set in code — is left alone, thresholds
    included (JAX's own variables cover them). Otherwise the cache goes
    to :data:`DEFAULT_CACHE_DIR` and keeps every entry: the ladder's rungs
    are small, fast compiles that JAX's default thresholds would skip.
    Returns the directory in use (None, with a warning, when the default
    cannot be created)."""
    global _cache_dir, _cache_configured
    with _cache_lock:
        if _cache_configured:
            return _cache_dir
        import jax
        _cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or jax.config.jax_compilation_cache_dir)
        if not _cache_dir:
            try:
                os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            except OSError as e:    # read-only checkout: say so, run cold
                logger.warning(
                    "no persistent compile cache: cannot create %s (%s); "
                    "set JAX_COMPILATION_CACHE_DIR to a writable directory",
                    DEFAULT_CACHE_DIR, e)
                _cache_configured = True
                return None
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            _cache_dir = DEFAULT_CACHE_DIR
        _cache_configured = True
        return _cache_dir


def _reset_cache_config_for_tests():
    """Forget the configured-once latch (test isolation only)."""
    global _cache_dir, _cache_configured
    with _cache_lock:
        _cache_dir = None
        _cache_configured = False


class BucketLadder:
    """Power-of-two batch buckets between ``min_batch_size`` and
    ``max_batch_size`` (inclusive; the top rung clamps to the max when the
    doubling overshoots). Incoming batches pad up to ``rung_for(n)`` with
    tail masking, so every request shape hits one of ``len(ladder)``
    executables instead of compiling per shape."""

    def __init__(self, min_batch_size: int,
                 max_batch_size: Optional[int] = None):
        mn = int(min_batch_size)
        mx = int(max_batch_size) if max_batch_size else mn
        if mn < 1:
            raise ValueError(f"min_batch_size must be >= 1, got {mn}")
        if mx < mn:
            raise ValueError(
                f"max_batch_size {mx} < min_batch_size {mn}")
        rungs: List[int] = []
        r = mn
        while r < mx:
            rungs.append(r)
            r *= 2
        rungs.append(mx)
        self.rungs: Tuple[int, ...] = tuple(rungs)

    @property
    def min(self) -> int:
        return self.rungs[0]

    @property
    def max(self) -> int:
        return self.rungs[-1]

    def rung_for(self, n: int) -> int:
        """Smallest rung that fits ``n`` records (the top rung for
        anything larger)."""
        for r in self.rungs:
            if n <= r:
                return r
        return self.rungs[-1]

    def up(self, rung: int) -> int:
        """The next larger rung (itself at the top)."""
        for r in self.rungs:
            if r > rung:
                return r
        return self.rungs[-1]

    def down(self, rung: int) -> int:
        """The next smaller rung (itself at the bottom)."""
        below = [r for r in self.rungs if r < rung]
        return below[-1] if below else self.rungs[0]

    def __contains__(self, n: int) -> bool:
        return int(n) in self.rungs

    def __iter__(self):
        return iter(self.rungs)

    def __len__(self) -> int:
        return len(self.rungs)

    def __repr__(self) -> str:
        return f"BucketLadder{self.rungs}"


def _pad_hist(site: str):
    return telemetry.get_registry().histogram(
        "zoo_bucket_pad_fraction",
        "Fraction of each dispatched bucket that is tail padding",
        ("site",), buckets=_PAD_BUCKETS).labels(site)


def pad_to_rung(arrays: Sequence[np.ndarray], rung: int,
                site: str = "inference") -> Tuple[np.ndarray, ...]:
    """Pad every array of one logical batch up to ``rung`` rows by
    repeating the last row (the caller masks the tail off the output).
    Records the padded fraction on ``zoo_bucket_pad_fraction{site=}`` for
    every call — a full batch observes 0, so the histogram's mean is the
    real pad-waste rate, not just the waste of padded batches."""
    arrays = tuple(arrays)
    n = int(arrays[0].shape[0])
    rung = int(rung)
    if n > rung:
        raise ValueError(f"batch of {n} does not fit rung {rung}")
    _pad_hist(site).observe((rung - n) / float(rung))
    if n == rung:
        return arrays
    return tuple(
        np.concatenate([a, np.repeat(a[-1:], rung - n, axis=0)])
        for a in arrays)


def batch_avals(spec: Sequence[Tuple[Tuple[int, ...], Any]], rung: int):
    """Turn a per-sample input spec — ``[(sample_shape, dtype), ...]``,
    one entry per model input — into batched ``jax.ShapeDtypeStruct``
    avals at batch size ``rung``."""
    import jax
    return tuple(jax.ShapeDtypeStruct((int(rung),) + tuple(shape), dtype)
                 for shape, dtype in spec)


def decode_grid_specs(spec, rungs, seq_rungs, avals_fn):
    """Enumerate the decode compile grid: for every (batch rung ×
    seq-length rung) pair, rewrite the LAST spec entry's time axis to the
    seq rung and yield ``avals_fn(dspec, rung)``. This is the one grid
    both ``warm_decode`` and the step scheduler's dispatch walk — the
    chunked-prefill buffers and the speculative k-wide verify step are
    just taller seq rungs on it, never new shapes."""
    dec_shape, dec_dtype = spec[-1]
    for rung in sorted({int(r) for r in rungs}):
        for sr in sorted({int(s) for s in seq_rungs}):
            dspec = spec[:-1] + (
                ((int(sr),) + tuple(dec_shape[1:]), dec_dtype),)
            yield avals_fn(dspec, rung)


def _aval_of(x):
    import jax
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        arr = np.asarray(x)
        shape, dtype = arr.shape, arr.dtype
    # a placed array keeps its sharding: lowered without it, the executable
    # expects the default device and rejects a mesh-placed argument on
    # every dispatch (seen with load_zoo params on a four-device mesh)
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=getattr(x, "sharding", None))


class ExecutableCache:
    """AOT-compiled executables for one jitted function, keyed by the
    avals signature of the call.

    ``__call__`` is the hot path: a warm signature dispatches directly
    through the stored compiled executable — bypassing ``jax.jit``'s
    dispatch cache entirely, so the ``zoo_jit_*`` recompile counters
    cannot move — and counts a ``zoo_compile_cache_hits_total``. A cold
    signature compiles synchronously (``zoo_compile_cache_misses_total``
    plus a timed ``zoo_compile_seconds`` observation) and is stored for
    next time. ``warm``/``warm_async`` pre-build rungs so the hot path
    never sees a cold signature; every compile — warm or miss — lands a
    ``compile`` span on the :data:`WARMUP_TRACE_ID` trace.

    Any failure in the AOT path (lowering, executable call) falls back to
    the plain jitted call, so the cache can only ever add speed, never
    break a model that jit handles — but never silently: each fallback
    dispatch is counted on :attr:`fallbacks` and the first one per
    signature is logged with its traceback. An executable that never
    matches its live call (sharding, layout, weak type) would otherwise
    recompile through jit on every dispatch and look like a slow model."""

    def __init__(self, jitted, name: str = "compile_ahead",
                 registry: Optional[telemetry.MetricsRegistry] = None,
                 tracer: Optional[telemetry.Tracer] = None):
        self._jitted = jitted
        self.name = name
        self._lock = threading.Lock()
        self._execs: Dict[Tuple, Any] = {}
        # CPU-fallback executables (ZOO_CPU_FALLBACK): same signatures,
        # compiled pinned to the host CPU device so serving can keep
        # answering while the accelerator does not
        self._cpu_execs: Dict[Tuple, Any] = {}
        self._inflight: set = set()
        #: dispatches and warm-ups that left the AOT path for plain jit
        self.fallbacks = 0
        #: XLA's FLOP count of the executable built last (None before)
        self.flops: Optional[float] = None
        self._fallback_sigs: set = set()
        reg = registry if registry is not None else telemetry.get_registry()
        self._tracer = tracer if tracer is not None else \
            telemetry.get_tracer()
        self._compile_hist = reg.histogram(
            "zoo_compile_seconds",
            "XLA compile time per AOT-built executable", ("fn",)
        ).labels(name)
        self._hits = reg.counter(
            "zoo_compile_cache_hits_total",
            "Dispatches served by an already-compiled executable",
            ("fn",)).labels(name)
        self._misses = reg.counter(
            "zoo_compile_cache_misses_total",
            "Dispatches that had to compile synchronously", ("fn",)
        ).labels(name)

    # ----------------------------------------------------------- keying
    @staticmethod
    def signature(args: Tuple) -> Tuple:
        """Pytree structure plus (shape, dtype) of every array leaf —
        the same avals identity ``jax.jit``'s cache keys on, so a stored
        executable is exactly reusable for a matching signature."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(telemetry._leaf_sig(leaf) for leaf in leaves))

    def ready(self, *args) -> bool:
        """True when a compiled executable exists for this call shape
        (``args`` may be concrete arrays or ``ShapeDtypeStruct`` avals —
        both carry the shape/dtype the signature reads)."""
        sig = self.signature(args)
        with self._lock:
            return sig in self._execs

    def __len__(self) -> int:
        with self._lock:
            return len(self._execs)

    # -------------------------------------------------------- compiling
    def _compile(self, sig: Tuple, avals: Tuple):
        """Build and store one executable; records the compile span +
        histogram. Duplicate concurrent builds of one signature are
        collapsed (second builder just waits for the dict entry)."""
        with self._lock:
            if sig in self._execs:
                return self._execs[sig]
            self._inflight.add(sig)
        try:
            t0 = perf_counter()
            lowered = self._jitted.lower(*avals)
            exe = lowered.compile()
            t1 = perf_counter()
            self._compile_hist.observe(t1 - t0)
            self._tracer.record(WARMUP_TRACE_ID, "compile", t0, t1)
            # the process keeps the executable's HLO text, FLOP count
            # and held-value counts under this cache's name:
            # profiling.scope_index and step_counts read them
            flops = profiling.note_executable(
                self.name, exe, fn=self._jitted, sig=sig, lowered=lowered)
            with self._lock:
                self._execs[sig] = exe
                self.flops = flops
            return exe
        finally:
            with self._lock:
                self._inflight.discard(sig)

    def _note_fallback(self, sig: Tuple, what: str) -> None:
        """Count one departure from the AOT path; log the first per
        signature with the active exception's traceback."""
        with self._lock:
            self.fallbacks += 1
            first = sig not in self._fallback_sigs
            self._fallback_sigs.add(sig)
        if first:
            logger.warning("%s: %s; this signature runs through plain jit "
                           "(may recompile per dispatch)", self.name, what,
                           exc_info=True)

    def warm(self, *avals) -> bool:
        """Synchronously AOT-compile one signature (no-op when already
        built). Returns True when an executable is available after the
        call; a failed compile counts as a fallback."""
        sig = self.signature(avals)
        with self._lock:
            if sig in self._execs:
                return True
        try:
            self._compile(sig, avals)
            return True
        except Exception:
            self._note_fallback(sig, "AOT warmup compile failed")
            return False

    def warm_cpu(self, *avals) -> bool:
        """AOT-compile one signature pinned to the host CPU device — the
        failover rung serving swaps to when the backend wedges. No-op when
        already built (or when no CPU device is visible). The name is
        load-bearing for zoolint's jit-compile-in-serve-loop rule: this is
        warmup, not hot-path compilation."""
        sig = self.signature(avals)
        with self._lock:
            if sig in self._cpu_execs:
                return True
        try:
            import jax
            cpu = jax.devices("cpu")[0]
            t0 = perf_counter()
            with jax.default_device(cpu):
                exe = self._jitted.lower(*avals).compile()
            t1 = perf_counter()
            self._compile_hist.observe(t1 - t0)
            self._tracer.record(WARMUP_TRACE_ID, "compile", t0, t1)
            with self._lock:
                self._cpu_execs[sig] = exe
            return True
        except Exception:
            logger.exception("CPU-fallback warmup compile failed for %s",
                             self.name)
            return False

    def cpu_ready(self, *args) -> bool:
        """True when a CPU-fallback executable exists for this shape."""
        sig = self.signature(args)
        with self._lock:
            return sig in self._cpu_execs

    def warm_async(self, aval_sets: Sequence[Tuple],
                   cpu_also: bool = False) -> threading.Thread:
        """Spawn a daemon thread that warms every signature in
        ``aval_sets`` (a list of argument-aval tuples), smallest first so
        the rung most likely to be needed next lands earliest. With
        ``cpu_also`` each rung's CPU-fallback executable is built right
        after its device one (failover is useless for rungs that would
        compile on the serve thread mid-wedge).

        After the rungs land, the thread also works off any queued kernel
        autotune requests (ops/autotune.py ``tune_pending``): shapes whose
        verdict was missing when a traced call first saw them get measured
        here, off the serve thread, so the next dispatch picks the tuned
        kernel without ever paying tuning latency in-band."""
        sets = [tuple(s) for s in aval_sets]

        def worker():
            for avals in sets:
                if _draining.is_set():
                    return
                self.warm(*avals)
                if cpu_also and not _draining.is_set():
                    self.warm_cpu(*avals)
            if _draining.is_set():
                return
            try:
                from analytics_zoo_tpu.ops import autotune
                autotune.tune_pending()
            except Exception:
                logger.exception("background autotune failed for %s",
                                 self.name)

        t = threading.Thread(target=worker, daemon=True,
                             name=f"zoo-warmup-{self.name}")
        t.start()
        register_warmup_thread(t)
        return t

    # --------------------------------------------------------- dispatch
    def __call__(self, *args):
        # fault-injection dispatch seam (suppressed when a DevicePipeline
        # already owns this logical dispatch — one arrival per batch)
        resilience.maybe_fault("dispatch")
        sig = self.signature(args)
        with self._lock:
            exe = self._execs.get(sig)
        if exe is None:
            self._misses.inc()
            try:
                exe = self._compile(sig, tree_avals(args))
            except Exception:
                # lowering failed (exotic leaf types, donated aliasing...):
                # the jitted call handles everything the cache can't
                self._note_fallback(sig, "AOT compile failed")
                return self._jitted(*args)
        else:
            self._hits.inc()
        try:
            return exe(*args)
        except Exception:
            # executable/arg mismatch (sharding drift, weak types): the
            # jitted path is always correct, just not compile-proof
            self._note_fallback(sig, "AOT executable rejected its call")
            return self._jitted(*args)

    def cpu_call(self, *args):
        """Dispatch through the CPU-fallback executable for this call's
        signature, building it first if warmup never got to this rung.
        Never consults the fault-injection dispatch seam: injected faults
        model the *accelerator*, and the whole point of this path is to
        keep serving while it does not answer."""
        sig = self.signature(args)
        with self._lock:
            exe = self._cpu_execs.get(sig)
        if exe is None:
            self.warm_cpu(*tree_avals(args))
            with self._lock:
                exe = self._cpu_execs.get(sig)
        if exe is not None:
            try:
                return exe(*args)
            except Exception:
                logger.exception("CPU-fallback executable call failed for "
                                 "%s; retrying via jit on the CPU device",
                                 self.name)
        import jax
        with jax.default_device(jax.devices("cpu")[0]):
            return self._jitted(*args)


def tree_avals(tree):
    """``tree`` as ``jax.ShapeDtypeStruct`` avals, each leaf keeping the
    sharding it was placed with."""
    import jax
    return jax.tree_util.tree_map(_aval_of, tree)
