"""The six per-layer metrics that split the decoder cells' two hot scopes
by kernel launch: attention into the flash launches and the work around
them, with the launches' time a grid step and the share of their score
pairs the mask wants; the expert layer into its grouped products and the
work around them. Readers ``kernel_ms`` and ``gauge_ratio`` on hand-made
events over scope indexes parsed from optimized HLO the v5e compiler
left, and the six entries as a ``benchmark`` PR will append them to
``BENCHMARK.json``."""

import json
import re
from pathlib import Path

import pytest

from benchmarks.harness import trace
from benchmarks.harness.manifest import Cell, metric_cells
from benchmarks.readers import gauge_ratio, kernel_ms, scope_ms

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = ROOT / "tests" / "fixtures"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
DECODERS = ["lfm2-8b-a1b-train-b2-s8192", "sdar-30b-a3b-train-b1-s8192"]
BERT = ["bert-base-train-s512", "bert-base-train-s128",
        "bert-base-train-b32-s128"]
SIX = ["attention_kernels_ms.train", "attention_around_ms.train",
       "flash_tile_us.train", "flash_useful_share.train",
       "moe_products_ms.train", "moe_around_ms.train"]
#: the last per-layer entry before these six
BEFORE = "corrupt_ms.train"
MS = 1_000_000


def _index(fixture: str) -> dict:
    from analytics_zoo_tpu.common import profiling
    return profiling.parse_scope_index((FIXTURES / fixture).read_text())


def _run(events, steps, batch, telemetry=None):
    """A traced training run as ``benchmarks/run.py`` hands it to the
    readers: ``events`` (name, start ms, duration ms) in one window."""
    ops = [(f"%{name} = f32[2]{{0}} custom-call()", int(s * MS), int(d * MS),
            "") for name, s, d in events]
    hi = max(o[1] + o[2] for o in ops) + MS
    summary = trace.TraceSummary({0: ops}, [], (0, hi), 1)
    return {"mode": "train", "trace": summary,
            "evidence": {"traced_units": steps * batch,
                         "telemetry": telemetry or {"start": {}, "end": {}}}}


def _read(cell, run, metric):
    spec = cell.metric_file(metric)
    reader = {"kernel_ms": kernel_ms, "scope_ms": scope_ms,
              "gauge_ratio": gauge_ratio}[spec["reader"]]
    return reader.read(cell, run, **spec["args"])


@pytest.fixture
def lfm2():
    return Cell(DECODERS[0])


def _laid_out(names, ms=1.0, gap=0.5):
    """One event a name, back to back with a gap between: no overlap."""
    return [(name, i * (ms + gap), ms) for i, name in enumerate(names)]


# ------------------------------------------------------------- attention

@pytest.fixture
def attention(monkeypatch):
    index = _index("hlo_v5e_live_tiles_attention_layer_entry.txt")
    monkeypatch.setattr(scope_ms, "load_index", lambda name: index)
    rx = re.compile(r"block_\d+/attention(/|$)")
    launches = [n for n, e in index.items() if "kernel" in e]
    around = [n for n, e in index.items() if "kernel" not in e
              and e["scope"] and rx.search(e["scope"])]
    assert len(launches) == 3 and len(around) >= 4
    return index, launches, around


def test_attention_is_its_launches_and_the_work_around_them(lfm2,
                                                            attention):
    """Over the live-tiles layer's index: the three flash launches (1 ms
    each) and four other ops of the attention scope (1 ms each), in two
    optimizer steps, none overlapping: the two halves add up to the
    union ``attention_ms`` reads."""
    index, launches, around = attention
    run = _run(_laid_out(launches + around[:4] + ["unknown.1"]), 2, 2)
    kernels = _read(lfm2, run, "attention_kernels_ms.train")
    rest = _read(lfm2, run, "attention_around_ms.train")
    assert kernels == pytest.approx(1.5) and rest == pytest.approx(2.0)
    assert kernels + rest == pytest.approx(
        _read(lfm2, run, "attention_ms.train"))


def test_ops_that_overlap_count_once_in_each_half(lfm2, attention):
    """An op around the launches that runs while a launch does counts in
    both halves and once in the union: the halves add up to more."""
    index, launches, around = attention
    events = _laid_out(launches) + [(around[0], 0.5, 1.0)]
    run = _run(events, 1, 2)
    kernels = _read(lfm2, run, "attention_kernels_ms.train")
    rest = _read(lfm2, run, "attention_around_ms.train")
    union = _read(lfm2, run, "attention_ms.train")
    assert (kernels, rest, union) == pytest.approx((3.0, 1.0, 3.5))


def test_flash_tile_us_divides_by_the_tiles_of_the_launches_that_ran(
        lfm2, attention):
    """Each launch of the layer lists 6 grid steps (two heads of one
    interior and two diagonal tiles). The forward launch ran twice in the
    stretch and each backward launch once, 2 ms each: 8 ms over 24
    steps."""
    index, launches, around = attention
    assert {index[n]["tiles"] for n in launches} == {6}
    fwd = next(n for n in launches if index[n]["kernel"] == "flash_fwd")
    events = _laid_out(launches + [fwd] + around[:2], ms=2.0)
    run = _run(events, 2, 2)
    assert _read(lfm2, run, "flash_tile_us.train") \
        == pytest.approx(1e3 * 8.0 / 24)
    # no launch in the stretch: nothing, not a division by nought
    assert _read(lfm2, _run(_laid_out(around[:2]), 2, 2),
                 "flash_tile_us.train") is None


# --------------------------------------------------------- expert layer

@pytest.fixture
def experts(monkeypatch):
    index = _index("hlo_v5e_expert_layer_entry.txt")
    monkeypatch.setattr(scope_ms, "load_index", lambda name: index)
    rx = re.compile(r"block_\d+/moe(/|$)")
    products = [n for n, e in index.items()
                if e.get("kernel") == "ragged_dot"]
    around = [n for n, e in index.items() if "kernel" not in e
              and e["scope"] and rx.search(e["scope"])]
    assert len(products) >= 4 and len(around) >= 10
    return index, products, around


def test_the_expert_layer_is_its_products_and_the_work_around_them(
        experts):
    """Over the expert layer's index: four grouped products (2 ms each)
    and ten other ops of the layer's scope (the sort, gathers, masks: 1 ms
    each) in one step: 8 + 10 ms, adding up to ``moe_ms``'s union."""
    index, products, around = experts
    events = _laid_out(products[:4], ms=2.0) + [
        (name, 20 + 1.5 * i, 1.0) for i, name in enumerate(around[:10])]
    for cell in map(Cell, DECODERS):
        run = _run(events, 1, int(cell.traffic["batch_size"]))
        got = (_read(cell, run, "moe_products_ms.train"),
               _read(cell, run, "moe_around_ms.train"))
        assert got == pytest.approx((8.0, 10.0))
        assert sum(got) == pytest.approx(_read(cell, run, "moe_ms.train"))


# ---------------------------------------------- what a parent gives

def test_every_reader_gives_nothing_on_an_index_without_kernels(
        lfm2, attention, monkeypatch):
    """A program from before the ``kernel`` key (the parent commit): every
    split reads None and the line leaves it out; ``attention_ms`` still
    reads. Without any index: None as well."""
    index, launches, around = attention
    run = _run(_laid_out(launches + around[:4]), 2, 2)
    bare = {n: {k: v for k, v in e.items() if k not in ("kernel", "tiles")}
            for n, e in index.items()}
    monkeypatch.setattr(scope_ms, "load_index", lambda name: bare)
    for metric in SIX:
        if metric != "flash_useful_share.train":
            assert _read(lfm2, run, metric) is None, metric
    assert _read(lfm2, run, "attention_ms.train") == pytest.approx(3.5)
    monkeypatch.setattr(scope_ms, "load_index", lambda name: None)
    assert _read(lfm2, run, "attention_kernels_ms.train") is None
    assert _read(lfm2, run, "flash_useful_share.train") is None


def test_flash_useful_share_is_allowed_over_computed(lfm2):
    """``zoo_flash_score_pairs`` at the window's end, as the compiled step
    publishes it: the three kernels of the block-diffusion cell's five
    layers, 32 heads, each 80 live tiles of 1,024 x 1,024 a head computed
    and 67,141,632 pairs a head allowed."""
    series = {}
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        key = f"executable=estimator_train_step,kernel={kernel}"
        series[f"{key},kind=computed"] = 5 * 32 * 83886080
        series[f"{key},kind=allowed"] = 5 * 32 * 67141632
    run = _run([("x.1", 0, 1)], 1, 2, {"start": {}, "end": {
        "zoo_flash_score_pairs": series}})
    assert _read(lfm2, run, "flash_useful_share.train") \
        == pytest.approx(100 * 67141632 / 83886080)
    assert round(_read(lfm2, run, "flash_useful_share.train"), 1) == 80.0
    nothing = {k: 0 for k in series}
    assert _read(lfm2, _run([("x.1", 0, 1)], 1, 2, {"start": {}, "end": {
        "zoo_flash_score_pairs": nothing}}), "flash_useful_share.train") \
        is None


# ------------------------------------------------------- the six entries
#
# The six wait outside ``BENCHMARK.json``: the accepted
# ``test_benchmark_lfm2_moe.py`` asks that ``lfm2``'s cell report its own
# nine per-layer metrics last, and ``test_benchmark_sdar_moe.py`` that no
# later metric list ``sdar``'s cell, so any metric appended for either cell
# fails one of them. A ``benchmark`` PR that edits both appends ``ENTRIES``
# as they stand (PERF.md, section 7).

#: the entries, in the order a ``benchmark`` PR appends them to ``per_layer``
ENTRIES = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": "kernels", "moves": "train_samples_per_s",
     "workloads": DECODERS}
    for name, unit, better, source in [
        ("attention_kernels_ms.train", "ms/step", "lower", "device_trace"),
        ("attention_around_ms.train", "ms/step", "lower", "device_trace"),
        ("flash_tile_us.train", "us/tile", "lower", "device_trace"),
        ("flash_useful_share.train", "%", "higher", "program_counter"),
        ("moe_products_ms.train", "ms/step", "lower", "device_trace"),
        ("moe_around_ms.train", "ms/step", "lower", "device_trace")]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _appended() -> dict:
    """``BENCHMARK.json`` with the six appended to ``per_layer``."""
    return dict(MANIFEST, per_layer=MANIFEST["per_layer"] + ENTRIES)


def test_the_six_are_appended_after_what_was_there():
    """Not yet entries of ``BENCHMARK.json``; appended, they stand after
    every entry it has, in their own order, under names it does not use."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert not set(SIX) & set(names)
    assert [m["name"] for m in ENTRIES] == SIX
    appended = [m["name"] for m in _appended()["per_layer"]]
    assert appended[-len(SIX):] == SIX
    assert appended.index(SIX[0]) > names.index(BEFORE)
    assert len(appended) == len(set(appended)) \
        and not set(SIX) & {m["name"] for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("metric", SIX)
def test_each_names_the_kernels_layer_and_the_two_decoder_cells(metric):
    """Each entry keeps ``BENCHMARK.json``'s rules for a per-layer metric
    (``test_benchmark_manifest.py``'s) and its file reads the scope of the
    union it splits."""
    manifest = _appended()
    m = next(x for x in ENTRIES if x["name"] == metric)
    assert m["workloads"] == DECODERS
    assert NAME.match(metric) and UNIT.match(m["unit"])
    assert m["layer"] in {x["layer"] for x in MANIFEST["per_layer"]}
    e2e = {x["name"]: x for x in manifest["end_to_end"]}
    assert m["moves"] == "train_samples_per_s" and m["moves"] in e2e
    assert set(metric_cells(m, manifest, "per_layer")) \
        <= set(metric_cells(e2e[m["moves"]], manifest, "end_to_end"))
    assert m["source"] == ("program_counter" if "share" in metric
                           else "device_trace")
    assert m["unit"] == {"flash_tile_us.train": "us/tile",
                         "flash_useful_share.train": "%"}.get(
                             metric, "ms/step")
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / f"{metric}.json").read_text())
    assert spec["reader"] in ("kernel_ms", "gauge_ratio")
    assert (ROOT / "benchmarks" / "readers"
            / f"{spec['reader']}.py").exists()
    # the halves read the scopes of the unions they split
    if spec["reader"] == "kernel_ms":
        whole = "attention_ms.train" if "attention" in metric \
            or "flash" in metric else "moe_ms.train"
        assert spec["args"]["pattern"] == json.loads(
            (ROOT / "benchmarks" / "metrics"
             / f"{whole}.json").read_text())["args"]["pattern"]


@pytest.mark.parametrize("cell", DECODERS + BERT)
def test_the_decoder_cells_report_the_six_and_the_bert_cells_none(cell):
    """Appended, the six are reported last in the decoder cells and in no
    BERT cell; today no cell reports them."""
    manifest = _appended()
    reported = [m["name"] for m in manifest["per_layer"]
                if cell in metric_cells(m, manifest, "per_layer")]
    if cell in DECODERS:
        assert reported[-len(SIX):] == SIX
    else:
        assert not set(SIX) & set(reported)
    assert not set(SIX) & {m["name"] for m in Cell(cell).per_layer}
