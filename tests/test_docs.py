"""Doc-test lane: every ```python block in docs/*.md actually executes.

The reference shipped a docs site whose snippets routinely rotted
(docs/docs/ProgrammingGuide); here the guides ARE tests — each document's
python blocks run top-to-bottom in one namespace in a fresh subprocess on
the virtual CPU mesh. Blocks marked ``<!-- doctest: skip -->`` on the line
directly above the fence are skipped (e.g. TPU-pod-only or
network-dependent snippets).
"""

import functools
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")

_FENCE = re.compile(
    r"(?P<skip><!--\s*doctest:\s*skip\s*-->\s*\n)?```python\n(?P<body>.*?)```",
    re.S)

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
# cwd stays the test tmpdir so snippets writing relative paths (ckpts/,
# tb_logs/) land there, never in the repo checkout
import sys
sys.path.insert(0, {repo!r})
"""


# docs whose snippets train real models for minutes on 1 CPU core — run
# them in the full lane only, not in tier-1/smoke (model-zoo ~100s,
# zouwu ~12s measured)
_SLOW_DOCS = {"model-zoo.md", "zouwu.md"}


def _doc_files():
    docs = sorted(f for f in os.listdir(DOCS) if f.endswith(".md"))
    return [pytest.param(d, marks=pytest.mark.slow) if d in _SLOW_DOCS
            else d for d in docs]


def extract_blocks(path):
    text = open(path).read()
    out = []
    for m in _FENCE.finditer(text):
        if not m.group("skip"):
            out.append(m.group("body"))
    return out


@pytest.mark.parametrize("doc", _doc_files())
def test_doc_snippets_execute(doc, tmp_path):
    blocks = extract_blocks(os.path.join(DOCS, doc))
    if not blocks:
        pytest.skip(f"{doc} has no python blocks")
    script = _PRELUDE.format(repo=REPO) + "\n\n".join(blocks)
    p = tmp_path / "doc_snippets.py"
    p.write_text(script)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["DOCTEST_TMPDIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, str(p)], capture_output=True,
                          text=True, timeout=1200, env=env,
                          cwd=str(tmp_path))
    assert proc.returncode == 0, (
        f"{doc} snippets failed:\n--- stdout ---\n{proc.stdout[-3000:]}\n"
        f"--- stderr ---\n{proc.stderr[-3000:]}")


def test_observability_catalog_matches_code():
    """Metric/env-var catalog drift (docs/observability.md vs the actual
    registrations and env reads) fails tier-1, not just the zoolint lane.
    zoolint's project-scope catalog rules are the single implementation —
    this test is just their pytest face (docs/zoolint.md)."""
    from analytics_zoo_tpu.analysis import catalog_drift
    findings = catalog_drift(root=REPO)
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------- names that must resolve

_CODE_SPAN = re.compile(r"`([^`\n]+)`")
_LINK = re.compile(r"\]\(([^)\s]+)\)")
#: what a name may carry behind the file: ``::test``, ``:118``, ``:72-85``
_SUFFIX = re.compile(r"(::[\w\[\]-]+|:\d+(-\d+)?(,\d+(-\d+)?)*)+$")
_CHECKED_EXT = (".py", ".sh", ".md")
_CHECKED_DIRS = ("tests/", "dev/", "docs/", "benchmarks/", "examples/",
                 "analytics_zoo_tpu/")
#: written at run time into directories .gitignore lists
_RUNTIME_DIRS = ("zoo_tpu_logs/", "build/", "native/build/", "chiprun_out/")
#: what a sentence says before a name it gives as the reference's own
_REFERENCE_LEAD = re.compile(r"\b(ref|reference|upstream)\b", re.I)
_SENTENCE_END = re.compile(r"\.\s|\n\s*\n|\n\s*[-*] |\|")
_WALKED = ("analytics_zoo_tpu", "tests", "benchmarks", "examples", "dev",
           "docs", "docker")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set(os.listdir(REPO))
    for top in _WALKED:
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    return names


def _resolves(name, doc_dir, basenames):
    if "/" not in name:
        return name in basenames
    name = name.rstrip("/")
    return any(os.path.exists(os.path.join(root, name))
               for root in (REPO, doc_dir,
                            os.path.join(REPO, "analytics_zoo_tpu")))


def _defines(name, members):
    """``tests/test_x.py::TestY::test_z``: the file defines each member."""
    if not members:
        return True
    with open(os.path.join(REPO, name)) as fh:
        source = fh.read()
    return all(re.search(rf"^\s*(def|class) {m}\b", source, re.M)
               for m in members)


def unresolved_names(path):
    """The names in one document that point at nothing in the checkout."""
    text = open(path).read()
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    doc_dir = os.path.dirname(path)
    basenames = _basenames()
    found = []
    for m in list(_CODE_SPAN.finditer(text)) + list(_LINK.finditer(text)):
        lead = _SENTENCE_END.split(text[max(0, m.start() - 400):m.start()])
        if _REFERENCE_LEAD.search(lead[-1]):
            continue
        for word in m.group(1).split():
            word = word.strip("()[],;\"'").split("#")[0]
            name = _SUFFIX.sub("", word)
            if not (name.endswith(_CHECKED_EXT)
                    or name.startswith(_CHECKED_DIRS)):
                continue
            if re.search(r"[*<>{}$]|\.\.\.|…|^[/~]|^\w+://", name) \
                    or name.startswith(_RUNTIME_DIRS):
                continue
            if not _resolves(name, doc_dir, basenames) \
                    or not _defines(name, re.findall(r"::(\w+)", word)):
                found.append(word)
    return found


def _named_documents():
    """The documents that describe the tree as it is (BASELINE.md and
    MIGRATION.md are tables of the reference's own paths; ROADMAP.md,
    PERF.md and CHANGES.md are history)."""
    return ["README.md", "PARITY.md", ".claude/skills/verify/SKILL.md"] \
        + sorted(os.path.join("docs", f) for f in os.listdir(DOCS)
                 if f.endswith(".md"))


@pytest.mark.parametrize("doc", _named_documents())
def test_names_of_scripts_and_documents_resolve(doc):
    """Every back-ticked word and every markdown link of a document that
    names a script or a document of this repository points at something
    in the checkout: a ``*.py``, ``*.sh`` or ``*.md`` (bare, it resolves
    against the root and every file name of the tree; with a directory,
    against the root, the document's own directory and
    ``analytics_zoo_tpu/``), or a path under ``tests/``, ``dev/``,
    ``docs/``, ``benchmarks/``, ``examples/``, ``analytics_zoo_tpu/``,
    with or without ``::test`` or ``:line`` behind it. A document that
    tells its reader to run a file that is gone fails here.

    Skipped by shape, never by name: patterns (``BENCH_r*.json``,
    ``<family>.py``, ``${VAR}``, ``...``), absolute paths and URLs, files
    written at run time under the git-ignored ``zoo_tpu_logs/``,
    ``build/`` and ``chiprun_out/``, fenced code blocks (the doc-test lane
    runs those), and names the sentence gives as the reference
    repository's (``ref``, ``reference`` or ``upstream`` earlier in the
    same sentence, list item or table cell)."""
    assert unresolved_names(os.path.join(REPO, doc)) == []


# ----------------------------------------------------- dev/run-tests.sh

RUN_TESTS = os.path.join(REPO, "dev", "run-tests.sh")


@functools.lru_cache(maxsize=None)
def _lanes():
    """{lane: the ``tests/...`` paths its branch of the ``case`` names}."""
    with open(RUN_TESTS) as fh:
        body = fh.read().split('case "$lane" in', 1)[1]
    lanes, lane = {}, None
    for line in body.splitlines():
        label = re.match(r"  ([\w-]+)\)", line)
        if label:
            lane = label.group(1)
        if lane and not line.lstrip().startswith("#"):
            lanes.setdefault(lane, []).extend(
                re.findall(r"\btests/[\w./-]*", line))
    return {k: v for k, v in lanes.items() if v}


@pytest.mark.parametrize("lane", sorted(_lanes()))
def test_lane_names_tests_that_exist(lane):
    """A lane of ``dev/run-tests.sh`` runs zoolint and pytest over files
    that are there, and the script parses."""
    for path in _lanes()[lane]:
        assert os.path.exists(os.path.join(REPO, path)), path
    subprocess.run(["bash", "-n", RUN_TESTS], check=True)
