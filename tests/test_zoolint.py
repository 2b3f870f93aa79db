"""zoolint unit tests — golden per-rule fixtures, suppression and
baseline round-trips, JSON schema stability, and the self-scan invariant
(the shipped tree is clean modulo dev/zoolint-baseline.json)."""

import json
import os
import textwrap

import pytest

from analytics_zoo_tpu.analysis import (
    all_rules, analyze_paths, analyze_source,
)
from analytics_zoo_tpu.analysis import baseline as baseline_lib
from analytics_zoo_tpu.analysis import report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "zoolint")


def _scan(source, relpath="serving/mod.py"):
    return analyze_source(textwrap.dedent(source), relpath)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------ rule catalog

def test_rule_registry_complete():
    rules = all_rules()
    assert set(rules) == {
        "wallclock-hotpath", "hotpath-host-sync",
        "jit-in-loop", "jit-call-inline", "jit-static-unhashable",
        "jit-compile-in-serve-loop",
        "engine-unlocked-write", "lock-order",
        "cross-thread-unlocked-state", "lock-order-inversion",
        "blocking-under-lock", "thread-leak",
        "metric-undocumented", "metric-undeclared", "envvar-undocumented",
        "rowwise-map-in-data-plane",
        "record-ack-leak", "lock-release-path", "span-pairing",
        "tainted-host-sync", "shape-dependent-branch-in-jit",
        "kv-page-leak",
    }
    for rid, rule in rules.items():
        assert rule.id == rid
        assert rule.scope in ("file", "project")
        assert rule.description


# --------------------------------------------------------------- wallclock

def test_wallclock_flagged_in_hot_path():
    src = """
    import time
    def stamp():
        return time.time()
    """
    (f,) = _scan(src, "analytics_zoo_tpu/serving/mod.py")
    assert f.rule == "wallclock-hotpath"
    assert f.line == 4


def test_wallclock_alias_and_datetime_resolved():
    src = """
    import time as clock
    import datetime
    def stamp():
        return clock.time(), datetime.datetime.now()
    """
    fs = _scan(src, "learn/mod.py")
    assert [f.rule for f in fs] == ["wallclock-hotpath"] * 2


def test_wallclock_ignored_outside_hot_path():
    src = """
    import time
    def stamp():
        return time.time()
    """
    assert _scan(src, "analytics_zoo_tpu/zouwu/mod.py") == []
    # perf_counter/monotonic are the sanctioned clocks
    ok = """
    import time
    def span():
        return time.perf_counter() - time.monotonic()
    """
    assert _scan(ok, "serving/mod.py") == []


# ----------------------------------------------------------- hotpath sync

def test_host_sync_in_dispatch_loop():
    src = """
    import jax
    import numpy as np
    def dispatch(batches):
        out = 0.0
        for b in batches:
            out += float(b.loss)
            out += b.loss.item()
            jax.block_until_ready(b)
            np.asarray(b)
        return out
    """
    fs = _scan(src)
    assert [f.rule for f in fs] == ["hotpath-host-sync"] * 4
    labels = "\n".join(f.message for f in fs)
    for needle in ("float(<non-literal>)", ".item()",
                   "jax.block_until_ready()", "numpy.asarray()"):
        assert needle in labels


def test_host_sync_requires_hot_function_and_loop():
    # same syncs, but the function name has no dispatch/drain/... token
    src = """
    import jax
    def summarize(batches):
        for b in batches:
            jax.block_until_ready(b)
    """
    assert _scan(src) == []
    # hot name but no loop: a single fence at the end is the sane pattern
    src = """
    import jax
    def drain(pending):
        jax.block_until_ready(pending)
    """
    assert _scan(src) == []


def test_host_sync_sampling_guard_does_not_exempt():
    """A fence on sampled steps only is still a fence: the fit loop's
    every-tenth-step one was a third of the device's idle time (PR 27)."""
    src = """
    import jax
    def run_epoch(steps, profiler):
        for s in steps:
            if profiler.should_sample():
                jax.block_until_ready(s)
    """
    assert _rules_of(_scan(src)) == ["hotpath-host-sync"]


def test_host_sync_float_of_literal_ok():
    src = """
    def step_loop(xs):
        acc = 0.0
        for x in xs:
            acc += float("1.5")
        return acc
    """
    assert _scan(src) == []


# ------------------------------------------------------------------- jit

def test_jit_in_loop():
    src = """
    import jax
    def build(fns):
        return [jax.jit(f) for f in fns]
    """
    # comprehensions are not For/While — only statement loops re-trace
    # per *iteration* in the way this rule targets
    src = """
    import jax
    def build(fns, xs):
        out = []
        for f in fns:
            out.append(jax.jit(f))
        return out
    """
    (f,) = _scan(src, "mod.py")
    assert f.rule == "jit-in-loop"


def test_jit_call_inline_and_from_import():
    src = """
    from jax import jit
    def apply(f, x):
        return jit(f)(x)
    """
    fs = _scan(src, "mod.py")
    assert "jit-call-inline" in _rules_of(fs)


def test_jit_static_unhashable_list_vs_tuple():
    src = """
    import jax
    bad = jax.jit(lambda a, b: a, static_argnums=[0])
    good = jax.jit(lambda a, b: a, static_argnums=(0,))
    named = jax.jit(lambda a, b: a, static_argnames=["b"])
    """
    fs = _scan(src, "mod.py")
    assert [f.rule for f in fs] == ["jit-static-unhashable"] * 2
    assert [f.line for f in fs] == [3, 5]


def test_local_helper_named_jit_not_flagged():
    src = """
    def jit(f):
        return f
    def apply(f, x):
        return jit(f)(x)
    """
    assert _scan(src, "mod.py") == []


# -------------------------------------------------- compile-in-serve-loop

def test_compile_in_serve_loop_flagged():
    src = """
    def serve_drain(jitted, rungs):
        out = []
        for avals in rungs:
            out.append(jitted.lower(*avals).compile())
        return out
    """
    fs = _scan(src)
    assert _rules_of(fs) == ["jit-compile-in-serve-loop"]
    assert len(fs) == 2   # .lower(*avals) AND the chained .compile()


def test_compile_in_serve_loop_baselines():
    # warm-named functions are the sanctioned AOT path; re.compile and
    # zero-arg str.lower() are not XLA builds; non-hot packages exempt
    src = """
    import re
    def warm_serve_loop(jitted, rungs):
        return [jitted.lower(*a).compile() for a in rungs]
    def produce(rows):
        for r in rows:
            if re.compile(r.pat):
                yield r.name.lower()
    """
    assert _scan(src) == []
    hot_elsewhere = """
    def serve_drain(jitted, rungs):
        out = []
        for avals in rungs:
            out.append(jitted.lower(*avals).compile())
        return out
    """
    assert _scan(hot_elsewhere, "analytics_zoo_tpu/zouwu/mod.py") == []


def test_compile_outside_loop_not_flagged():
    # one build at function entry (the ExecutableCache miss path) is fine
    src = """
    def predict(jitted, avals, x):
        exe = jitted.lower(*avals).compile()
        return exe(x)
    """
    assert _scan(src) == []


# ----------------------------------------------------------- concurrency

def test_unlocked_write_across_thread_boundary():
    src = """
    import threading
    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
        def start(self):
            threading.Thread(target=self._run).start()
        def _run(self):
            self.n += 1
        def read(self):
            self.n = 0
    """
    fs = _scan(src, "mod.py")
    assert [f.rule for f in fs] == ["engine-unlocked-write"] * 2


def test_locked_write_is_clean():
    src = """
    import threading
    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
        def start(self):
            threading.Thread(target=self._run).start()
        def _run(self):
            with self._lock:
                self.n += 1
        def read(self):
            with self._lock:
                return self.n
    """
    assert _scan(src, "mod.py") == []


def test_thread_confined_attr_is_clean():
    # only the thread side touches _streak: no sharing, no finding
    src = """
    import threading
    class Engine:
        def __init__(self):
            self._streak = 0
        def start(self):
            threading.Thread(target=self._run).start()
        def _run(self):
            self._streak += 1
    """
    assert _scan(src, "mod.py") == []


def test_lock_order_inversion():
    src = """
    class M:
        def fwd(self):
            with self.a_lock:
                with self.b_lock:
                    pass
        def bwd(self):
            with self.b_lock:
                with self.a_lock:
                    pass
    """
    fs = _scan(src, "mod.py")
    assert _rules_of(fs) == ["lock-order"]
    src_consistent = """
    class M:
        def fwd(self):
            with self.a_lock:
                with self.b_lock:
                    pass
        def also_fwd(self):
            with self.a_lock:
                with self.b_lock:
                    pass
    """
    assert _scan(src_consistent, "mod.py") == []


# --------------------------------------------------- rowwise in data plane

def test_rowwise_map_flagged_in_data_plane():
    src = """
    def pad(d, seq_len):
        d["h"] = d["h"].map(lambda h: list(h)[:seq_len])
        return d
    """
    (f,) = _scan(src, "analytics_zoo_tpu/data/mod.py")
    assert f.rule == "rowwise-map-in-data-plane"
    assert f.line == 3
    # friesian/ is the other data-plane tree
    (f,) = _scan(src, "analytics_zoo_tpu/friesian/feature/mod.py")
    assert f.rule == "rowwise-map-in-data-plane"


def test_rowwise_nested_def_and_apply_axis1_flagged():
    src = """
    def xform(d):
        def pad_one(h):
            return list(h) + [0]
        d["h"] = d["h"].map(pad_one)
        d["t"] = d.apply(lambda r: sum(r.values), axis=1)
        d["u"] = d.apply(lambda r: sum(r.values), axis="columns")
        return d
    """
    fs = _scan(src, "analytics_zoo_tpu/data/mod.py")
    assert [f.rule for f in fs] == ["rowwise-map-in-data-plane"] * 3


def test_rowwise_dict_param_and_axis0_not_flagged():
    src = """
    def xform(d, func, mapping):
        d["e"] = d["e"].map(mapping)       # param: udf seam, caller's call
        d["f"] = d["f"].map({1: 2})        # dict map: vectorized lookup
        d["g"] = d["g"].map(len)           # builtin, not a nested def
        d["s"] = d.apply(sum)              # column-wise apply
        return d
    """
    assert _scan(src, "analytics_zoo_tpu/data/mod.py") == []


def test_rowwise_silent_outside_data_plane():
    src = """
    def pad(d, seq_len):
        d["h"] = d["h"].map(lambda h: list(h)[:seq_len])
        return d
    """
    assert _scan(src, "analytics_zoo_tpu/zouwu/mod.py") == []
    assert _scan(src, "analytics_zoo_tpu/serving/mod.py") == []


def test_rowwise_inline_suppression():
    src = """
    def pad(d, seq_len):
        d["h"] = d["h"].map(  # zoolint: disable=rowwise-map-in-data-plane
            lambda h: list(h))
        return d
    """
    assert _scan(src, "analytics_zoo_tpu/data/mod.py") == []


# ---------------------------------------------------------- suppressions

def test_line_suppression_bare_and_named():
    src = """
    import time
    def stamp():
        a = time.time()  # zoolint: disable
        b = time.time()  # zoolint: disable=wallclock-hotpath
        c = time.time()  # zoolint: disable=jit-in-loop
        return a, b, c
    """
    fs = _scan(src)
    assert len(fs) == 1 and fs[0].line == 6


def test_file_suppression():
    src = """
    # zoolint: disable-file=wallclock-hotpath
    import time
    def stamp():
        return time.time()
    """
    assert _scan(src) == []


# -------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    mod = tmp_path / "serving" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import time\n\n\ndef stamp():\n"
                   "    return time.time()\n")
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    assert _rules_of(findings) == ["wallclock-hotpath"]

    bl = tmp_path / "baseline.json"
    n = baseline_lib.save(str(bl), findings, str(tmp_path),
                          justifications=None)
    assert n == 1
    entries = baseline_lib.load(str(bl))
    left, stale = baseline_lib.apply(findings, entries, str(tmp_path))
    assert left == [] and stale == []

    # fingerprints key on line *text*, not line number: shifting the
    # offending line down must not invalidate the baseline ...
    mod.write_text("import time\n\n# a new comment\n\n\ndef stamp():\n"
                   "    return time.time()\n")
    findings2 = analyze_paths([str(mod)], root=str(tmp_path))
    left, stale = baseline_lib.apply(findings2, entries, str(tmp_path))
    assert left == [] and stale == []

    # ... while editing the line itself retires the entry (stale) and
    # resurfaces the finding
    mod.write_text("import time\n\n\ndef stamp():\n"
                   "    return time.time() + 0\n")
    findings3 = analyze_paths([str(mod)], root=str(tmp_path))
    left, stale = baseline_lib.apply(findings3, entries, str(tmp_path))
    assert len(left) == 1 and len(stale) == 1


def test_baseline_preserves_justifications(tmp_path):
    mod = tmp_path / "common" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import time\nT = time.time()\n")
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    bl = str(tmp_path / "baseline.json")
    baseline_lib.save(bl, findings, str(tmp_path))
    entries = baseline_lib.load(bl)
    fp = next(iter(entries))
    entries[fp]["justification"] = "module-load timestamp, not a loop"
    with open(bl, "w") as fh:
        json.dump({"version": baseline_lib.BASELINE_VERSION,
                   "entries": list(entries.values())}, fh)
    baseline_lib.save(bl, findings, str(tmp_path))
    again = baseline_lib.load(bl)
    assert again[fp]["justification"] == \
        "module-load timestamp, not a loop"


def test_baseline_rejects_unknown_version(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text('{"version": 99, "entries": []}')
    with pytest.raises(ValueError):
        baseline_lib.load(str(bl))


# ---------------------------------------------------------- JSON schema

def test_json_report_schema(tmp_path):
    mod = tmp_path / "learn" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import time\nT = time.time()\n")
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    obj = json.loads(report.json_report(
        findings, [{"fingerprint": "deadbeefdeadbeef"}], str(tmp_path)))
    assert obj["version"] == report.JSON_SCHEMA_VERSION == 1
    assert set(obj) == {"version", "findings", "stale_baseline", "summary"}
    (f,) = obj["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message",
                      "fingerprint"}
    assert f["path"] == "learn/mod.py"
    assert obj["stale_baseline"] == ["deadbeefdeadbeef"]
    assert obj["summary"] == {"total": 1,
                              "by_rule": {"wallclock-hotpath": 1}}


# ----------------------------------------------------- tree + fixture scan

def test_shipped_tree_clean_modulo_baseline():
    findings = analyze_paths([os.path.join(REPO, "analytics_zoo_tpu")],
                             root=REPO)
    entries = baseline_lib.load(
        os.path.join(REPO, baseline_lib.DEFAULT_BASELINE))
    left, _stale = baseline_lib.apply(findings, entries, REPO)
    assert left == [], "\n".join(f.format() for f in left)
    for e in entries.values():
        assert e["justification"].strip() and \
            not e["justification"].startswith("TODO"), e


def test_seeded_fixture_trips_every_family():
    findings = analyze_paths([FIXTURE], root=REPO)
    got = set(_rules_of(findings))
    # metric-undeclared can't fire here by design: the fixture scan does
    # not cover analytics_zoo_tpu/, so doc-side rows are not checked
    assert got == {
        "wallclock-hotpath", "hotpath-host-sync",
        "jit-in-loop", "jit-call-inline", "jit-static-unhashable",
        "jit-compile-in-serve-loop",
        "engine-unlocked-write", "lock-order",
        "cross-thread-unlocked-state", "lock-order-inversion",
        "blocking-under-lock", "thread-leak",
        "metric-undocumented", "envvar-undocumented",
        "rowwise-map-in-data-plane",
        "record-ack-leak", "lock-release-path", "span-pairing",
        "tainted-host-sync", "shape-dependent-branch-in-jit",
        "kv-page-leak",
    }
    # and the suppressed half of the fixture stays quiet
    sup = [f for f in findings
           if f.path.endswith("bad_hotpath.py") and f.line >= 25]
    assert sup == []


def test_metric_undeclared_requires_full_package_scan(tmp_path):
    # a doc row with no registration fires on a whole-package scan ...
    pkg = tmp_path / "analytics_zoo_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "serving").mkdir()
    (pkg / "serving" / "mod.py").write_text("X = 1\n")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(
        "| `zoo_ghost_total` | counter |\n")
    fs = analyze_paths([str(pkg)], root=str(tmp_path))
    assert [f.rule for f in fs] == ["metric-undeclared"]
    # ... but a subtree scan must not flag metrics registered elsewhere
    fs = analyze_paths([str(pkg / "serving")], root=str(tmp_path))
    assert fs == []


def test_fleet_fixture_trips_metric_undeclared():
    """The on-disk seeded fixture for the catalog rule the main fixture
    can't fire (ISSUE 6): a documented ``zoo_fleet_*`` metric that no
    code registers must read ``metric-undeclared`` on a full-package
    scan of the fixture root."""
    root = os.path.join(REPO, "tests", "fixtures", "zoolint_fleet")
    fs = analyze_paths([os.path.join(root, "analytics_zoo_tpu")],
                       root=root)
    undeclared = [f for f in fs if f.rule == "metric-undeclared"]
    assert len(undeclared) == 1, [f.format() for f in fs]
    assert "zoo_fleet_ghost_total" in undeclared[0].message
    # the registered-and-documented twin stays clean
    assert not any("zoo_fleet_present_total" in f.message for f in fs)


def test_cli_partial_scan_keeps_baseline_quiet(monkeypatch, capsys):
    # gan.py's baselined findings are out of scope when scanning
    # serving/ only — neither surfaced nor reported stale
    from analytics_zoo_tpu.analysis import cli
    monkeypatch.chdir(REPO)
    rc = cli.main(["analytics_zoo_tpu/serving"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "stale" not in out


def _cli_tree(tmp_path):
    """A minimal anchored checkout with one wallclock finding."""
    (tmp_path / ".git").mkdir()
    mod = tmp_path / "serving" / "mod.py"
    mod.parent.mkdir()
    mod.write_text("import time\n\n\ndef stamp():\n"
                   "    return time.time()\n")
    return mod


def test_cli_github_format(tmp_path, capsys):
    from analytics_zoo_tpu.analysis import cli
    mod = _cli_tree(tmp_path)
    rc = cli.main(["--no-baseline", "--format=github", str(mod)])
    out = capsys.readouterr().out
    assert rc == 1
    line = out.strip().splitlines()[0]
    assert line.startswith("::error file=serving/mod.py,line=5,")
    assert "title=zoolint wallclock-hotpath" in line
    # clean scans emit a notice, not silence
    (tmp_path / "clean.py").write_text("X = 1\n")
    rc = cli.main(["--no-baseline", "--format=github",
                   str(tmp_path / "clean.py")])
    out = capsys.readouterr().out
    assert rc == 0 and "::notice" in out


def test_cli_exit_codes_distinguish_usage_and_crash(monkeypatch, capsys):
    from analytics_zoo_tpu.analysis import cli
    # usage error: 2
    assert cli.main(["/no/such/path.py"]) == 2
    assert cli.main(["--rules", "bogus-rule", "."]) == 2
    # internal crash: 3 (so CI can tell findings from linter bugs)
    def boom(*a, **k):
        raise RuntimeError("linter bug")
    monkeypatch.setattr(cli, "analyze_paths", boom)
    assert cli.main(["--no-baseline", "."]) == 3
    err = capsys.readouterr().err
    assert "internal error" in err and "RuntimeError" in err


def test_cli_jobs_parallel_matches_serial(capsys):
    from analytics_zoo_tpu.analysis import cli
    args = ["--no-baseline", "--format=json", FIXTURE]
    rc1 = cli.main(["--jobs", "1"] + args)
    out1 = capsys.readouterr().out
    rc4 = cli.main(["--jobs", "4"] + args)
    out4 = capsys.readouterr().out
    assert rc1 == rc4 == 1
    assert json.loads(out1) == json.loads(out4)


def test_cli_migrate_baseline_v1_to_v2(tmp_path, capsys):
    from analytics_zoo_tpu.analysis import cli
    mod = _cli_tree(tmp_path)
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    (f, fp1), = baseline_lib.fingerprints(findings, str(tmp_path),
                                          version=1)
    bl = tmp_path / "dev" / "zoolint-baseline.json"
    bl.parent.mkdir()
    bl.write_text(json.dumps({"version": 1, "entries": [{
        "fingerprint": fp1, "rule": f.rule, "path": f.path,
        "line": f.line, "message": f.message,
        "justification": "known wallclock, kept on purpose"}]}))
    # a normal run refuses the v1 file with a pointer at the migration
    assert cli.main([str(mod)]) == 2
    assert "--migrate-baseline" in capsys.readouterr().err
    # one-shot migration preserves the justification ...
    assert cli.main(["--migrate-baseline", str(mod)]) == 0
    assert "migrated" in capsys.readouterr().out
    entries = baseline_lib.load(str(bl))
    (entry,) = entries.values()
    assert entry["justification"] == "known wallclock, kept on purpose"
    # ... and the migrated baseline keeps the tree quiet across a rewrap
    assert cli.main([str(mod)]) == 0
    capsys.readouterr()
    mod.write_text("import time\n\n\ndef stamp():\n"
                   "    return max(time.time(),\n               0 * 1)\n")
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    bl.write_text(json.dumps({"version": 2, "entries": [
        dict(e, fingerprint=fp) for (_f, fp), e in
        zip(baseline_lib.fingerprints(findings, str(tmp_path)),
            entries.values())]}))
    mod.write_text("import time\n\n\ndef stamp():\n"
                   "    return max(time.time(), 0 * 1)\n")
    findings2 = analyze_paths([str(mod)], root=str(tmp_path))
    left, stale = baseline_lib.apply(
        findings2, baseline_lib.load(str(bl)), str(tmp_path))
    assert left == [] and stale == []


def test_cli_ownership_report(tmp_path, capsys):
    from analytics_zoo_tpu.analysis import cli
    _cli_tree(tmp_path)
    out_md = tmp_path / "docs" / "concurrency.md"
    rc = cli.main(["--ownership-report", str(out_md),
                   str(tmp_path / "serving")])
    assert rc == 0
    assert "ownership report written" in capsys.readouterr().out
    assert out_md.is_file()
    js = json.loads((tmp_path / "docs" / "concurrency.json").read_text())
    assert [r["root"] for r in js["roots"]][0] == "main"


def test_syntax_error_is_a_finding(tmp_path):
    mod = tmp_path / "broken.py"
    mod.write_text("def broken(:\n")
    findings = analyze_paths([str(mod)], root=str(tmp_path))
    assert [f.rule for f in findings] == ["syntax-error"]
