"""Hot-path sync rules — wall-clock timing and implicit host↔device
synchronization in the serve/dispatch/train inner loops.

These replace dev/run-tests.sh's ``lint_wallclock`` grep and extend it to
the bug class the Gemma-on-TPU comparison (PAPERS.md) blames for most
GPU→TPU regressions: a single accidental host round-trip (``.item()``,
``float(device_val)``, ``np.asarray``, a ``block_until_ready``) inside a
dispatch loop serializes the host against the device and erases the
overlap the pipeline PRs bought. A fence "only on sampled steps" is one
too: the fit loop's profiler fenced every tenth step to time it and that
alone was a third of the device's idle time (PERF.md, PR 27), so no
sampling predicate excuses one.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from analytics_zoo_tpu.analysis.core import (
    FileContext, Finding, Rule, ancestors, register,
)

#: wall-clock constructors banned from hot-path packages (stage stats and
#: deadlines must ride perf_counter/monotonic — NTP slew corrupts both)
_WALLCLOCK = frozenset({
    "time.time", "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.datetime.today", "datetime.date.today",
})

#: function-name tokens that mark a dispatch/drain/step loop owner — the
#: loops inside these are the latency-critical inner loops
HOT_FN_TOKENS = frozenset({
    "dispatch", "drain", "step", "serve", "retire", "submit", "produce",
    "finish", "fetch", "run", "predict", "fit", "loop",
})

#: callee final components that force a host sync wherever they resolve
#: from (jax.device_get, telemetry.traced_device_get, bare imports...)
_SYNC_TAILS = frozenset({
    "block_until_ready", "device_get", "traced_device_get",
})
#: fully-resolved names that force a host copy of their argument
_SYNC_CALLS = frozenset({"numpy.asarray", "numpy.array"})

_LOOPS = (ast.For, ast.While, ast.AsyncFor, ast.ListComp, ast.SetComp,
          ast.DictComp, ast.GeneratorExp)
_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)

def _fn_tokens(name: str) -> set:
    return set(t for t in name.lower().split("_") if t)


def _enclosing(node: ast.AST, kinds) -> List[ast.AST]:
    return [a for a in ancestors(node) if isinstance(a, kinds)]


def _nearest_function(node: ast.AST):
    for a in ancestors(node):
        if isinstance(a, _FUNCS):
            return a
    return None


@register
class WallclockHotpath(Rule):
    """``time.time()`` / ``datetime.now()`` in serving/, common/, learn/.

    Wall-clock stamps there corrupt stage stats, deadlines and rate
    limiters under NTP slew — use ``time.perf_counter()`` (intervals) or
    ``time.monotonic()`` (deadlines). Legitimate wall-clock uses (event
    timestamps, dump filenames, checkpoint metadata) carry
    ``# zoolint: disable=wallclock-hotpath``."""

    id = "wallclock-hotpath"
    description = "wall-clock timing in a hot-path package"

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.is_hot_path:
            return
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            name = ctx.imports.resolve(node.func)
            if name in _WALLCLOCK:
                yield Finding(
                    self.id, ctx.path, node.lineno, node.col_offset,
                    f"{name}() in a hot-path package — use "
                    "time.perf_counter() for intervals or "
                    "time.monotonic() for deadlines")


@register
class HotpathHostSync(Rule):
    """Implicit host↔device sync inside a dispatch/drain/step loop.

    Flags ``.item()``, ``float(x)``, ``np.asarray``/``np.array``,
    ``device_get`` and ``block_until_ready`` calls that sit lexically
    inside a loop of a hot-named function
    (dispatch/drain/serve/produce/finish/fetch/run/predict/fit/...)
    in a hot-path package. Each one forces the host to wait for the
    device per iteration — exactly what the bounded in-flight window
    exists to avoid. Fence off-loop or fetch via the pipeline's drain;
    time the device from a profiler trace, not with a fence."""

    id = "hotpath-host-sync"
    description = "implicit device sync inside a hot dispatch loop"

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.is_hot_path:
            return
        for node in ctx.walk():
            if not isinstance(node, ast.Call):
                continue
            label = self._sync_label(ctx, node)
            if label is None:
                continue
            fn = _nearest_function(node)
            if fn is None or not (_fn_tokens(fn.name) & HOT_FN_TOKENS):
                continue
            loops = [lp for lp in _enclosing(node, _LOOPS)
                     if _nearest_function(lp) is fn]
            if not loops:
                continue
            yield Finding(
                self.id, ctx.path, node.lineno, node.col_offset,
                f"{label} inside the `{fn.name}` loop forces a host sync "
                "per iteration — hoist it out of the loop or use the "
                "pipeline drain")

    @staticmethod
    def _sync_label(ctx: FileContext, node: ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args and not node.keywords:
            return ".item()"
        name = ctx.imports.resolve(func)
        if name and (name.split(".")[-1] in _SYNC_TAILS
                     or name in _SYNC_CALLS):
            return f"{name}()"
        if name == "float" and len(node.args) == 1 \
                and not isinstance(node.args[0], ast.Constant):
            return "float(<non-literal>)"
        return None
