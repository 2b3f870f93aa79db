"""Parallel data-plane executor + tiered-pipeline tests.

Proves the properties the shard executor claims: ordered results with a
bounded in-flight window (a full ``DISK_n``/``NATIVE_n`` Friesian pipeline
never gathers the table and never holds more than ``workers + 2`` shards in
flight), shard exceptions that carry the failing index, the map-reduce
seam, first()-based metadata, transient zip/column views that don't
re-spill, repartition/partition_by row parity, parquet write modes, and the
streaming prefetch depth knob.
"""

import glob
import os

import numpy as np
import pandas as pd
import pytest

from analytics_zoo_tpu.common.context import OrcaContext
from analytics_zoo_tpu.data import shard as shard_lib
from analytics_zoo_tpu.data.shard import HostXShards, ShardTransformError
from analytics_zoo_tpu.friesian.feature import FeatureTable


@pytest.fixture
def parallel_env(monkeypatch):
    monkeypatch.setenv("ZOO_DATA_WORKERS", "3")
    monkeypatch.setenv("ZOO_DATA_VECTORIZE", "1")


@pytest.fixture
def tier(request):
    old = OrcaContext.train_data_store
    OrcaContext.train_data_store = request.param
    yield request.param
    OrcaContext.train_data_store = old


def _frames(n=8, rows=16):
    rng = np.random.RandomState(7)
    return [pd.DataFrame({
        "user": rng.randint(0, 5, rows),
        "item": rng.randint(0, 9, rows),
        "cat": [["a", "b", "c", "d"][j % 4] for j in range(rows)],
        "hist": [list(range(j % 4)) for j in range(rows)],
    }) for _ in range(n)]


# ------------------------------------------------------------- executor

def test_executor_results_stay_ordered(parallel_env):
    import time as _t
    xs = HostXShards([{"i": i} for i in range(12)])

    def slow_when_early(s):
        _t.sleep(0.02 if s["i"] < 3 else 0)   # early shards finish last
        return {"i": s["i"] * 10}
    out = xs.transform_shard(slow_when_early).collect()
    assert [s["i"] for s in out] == [i * 10 for i in range(12)]
    stats = shard_lib.LAST_RUN_STATS["transform_shard"]
    assert 1 <= stats["in_flight_peak"] <= stats["workers"] + 2


def test_executor_propagates_shard_index(parallel_env):
    xs = HostXShards([{"i": i} for i in range(8)])

    def boom(s):
        if s["i"] == 5:
            raise ValueError("bad shard content")
        return s
    with pytest.raises(ShardTransformError) as ei:
        xs.transform_shard(boom).collect()
    assert ei.value.shard_index == 5
    assert ei.value.op == "transform_shard"
    assert "ValueError" in str(ei.value)
    # the serial path reports the same index
    os.environ["ZOO_DATA_WORKERS"] = "0"
    with pytest.raises(ShardTransformError) as ei:
        xs.transform_shard(boom).collect()
    assert ei.value.shard_index == 5


def test_map_reduce_shard(parallel_env):
    xs = HostXShards(_frames(6))
    total = xs.map_reduce_shard(len, lambda a, b: a + b)
    assert total == sum(len(f) for f in _frames(6))
    with pytest.raises(ShardTransformError):
        xs.map_reduce_shard(lambda d: d["missing"].sum(),
                            lambda a, b: a + b)


def test_first_fetches_only_shard_zero():
    xs = HostXShards(_frames(4))
    fetched = []
    orig = xs._store.get
    xs._store.get = lambda i: (fetched.append(i), orig(i))[1]
    assert len(xs.first()) == 16
    assert fetched == [0]
    with pytest.raises(IndexError):
        HostXShards([]).first()


# --------------------------------------------------- tiered full pipeline

@pytest.mark.parametrize("tier", ["DISK_2", "NATIVE_2"], indirect=True)
def test_full_pipeline_bounded_no_gather(tier, parallel_env, monkeypatch):
    """gen_string_idx fit + encode + pad over a spill tier: completes with
    a bounded in-flight window and no silent whole-table gather."""
    gathers = []
    monkeypatch.setattr(
        HostXShards, "collect",
        lambda self: gathers.append(self) or [
            self._store.get(i) for i in range(self.num_partitions())])

    t = FeatureTable.from_pandas(pd.concat(_frames(8), ignore_index=True), 8)
    assert t.shards.tier.split("_")[0] in ("DISK", "NATIVE")
    [idx] = t.gen_string_idx("cat")
    out = t.encode_string("cat", [idx]).pad("hist", seq_len=4)
    # the only gather so far is the 1-shard StringIndex (to_dict); the
    # 8-shard data table is never materialized
    assert all(g.num_partitions() == 1 for g in gathers)
    for op in ("gen_string_idx", "encode_string", "pad"):
        stats = shard_lib.LAST_RUN_STATS.get(op)
        if stats is not None:
            assert stats["in_flight_peak"] <= stats["workers"] + 2, op
    n_before = len(gathers)
    df = out.to_pandas()          # the one sanctioned data gather, at the end
    assert len(gathers) == n_before + 1
    assert set(df["cat"].unique()) <= {1, 2, 3, 4}
    assert all(len(h) == 4 for h in df["hist"])


def test_zip_and_getitem_are_transient(parallel_env):
    old = OrcaContext.train_data_store
    OrcaContext.train_data_store = "DISK_2"
    try:
        xs = HostXShards([{"x": np.arange(4) + i} for i in range(4)])
        ys = HostXShards([{"y": np.arange(4) * i} for i in range(4)])
        assert xs.tier == "DISK_2"
        zipped = xs.zip(ys)
        # views of already-stored shards: never re-spilled
        assert zipped.tier == "DRAM"
        for i, (a, b) in enumerate(zipped.collect()):
            np.testing.assert_array_equal(a["x"], np.arange(4) + i)
            np.testing.assert_array_equal(b["y"], np.arange(4) * i)
        col = xs["x"]
        assert col.tier == "DRAM"
        np.testing.assert_array_equal(col.collect()[2], np.arange(4) + 2)
    finally:
        OrcaContext.train_data_store = old


def test_zip_rejects_mismatched_partitions():
    xs = HostXShards([{"x": np.arange(4)}] * 2)
    with pytest.raises(AssertionError):
        xs.zip(HostXShards([{"y": np.arange(4)}] * 3))


# -------------------------------------------- repartition / partition_by

@pytest.mark.parametrize("m", [1, 2, 5, 11])
def test_repartition_preserves_rows_dataframes(parallel_env, m):
    frames = _frames(4, rows=10)
    xs = HostXShards([f.copy() for f in frames])
    out = xs.repartition(m)
    assert out.num_partitions() == m
    got = pd.concat(out.collect(), ignore_index=True)
    want = pd.concat(frames, ignore_index=True)
    pd.testing.assert_frame_equal(got, want)


def test_repartition_np_dict_and_records(parallel_env):
    xs = HostXShards([{"x": np.arange(6) + 10 * i,
                       "y": np.ones(6) * i} for i in range(3)])
    out = xs.repartition(2).collect()
    np.testing.assert_array_equal(
        np.concatenate([s["x"] for s in out]),
        np.concatenate([np.arange(6) + 10 * i for i in range(3)]))
    rec = HostXShards([[1, 2, 3], [4, 5], [6]])
    assert [r for s in rec.repartition(2).collect() for r in s] == \
        [1, 2, 3, 4, 5, 6]


def test_partition_by_groups_and_preserves_rows(parallel_env):
    frames = _frames(5)
    xs = HostXShards([f.copy() for f in frames])
    out = xs.partition_by("user", 3)
    assert out.num_partitions() == 3
    shards = out.collect()
    seen = {}
    for i, s in enumerate(shards):
        for u in s["user"].unique():
            assert seen.setdefault(u, i) == i, "user split across shards"
    got = pd.concat(shards).sort_values(["user", "item"]).reset_index(
        drop=True)
    want = pd.concat(frames).sort_values(["user", "item"]).reset_index(
        drop=True)
    pd.testing.assert_frame_equal(got, want)


# ----------------------------------------------------- parquet + metadata

def test_write_parquet_modes(tmp_path):
    t3 = FeatureTable.from_pandas(
        pd.DataFrame({"a": np.arange(9)}), 3)
    p = str(tmp_path / "t")
    t3.write_parquet(p)
    assert len(glob.glob(os.path.join(p, "part-*.parquet"))) == 3
    # overwrite with fewer shards clears the stale third part file
    t2 = FeatureTable.from_pandas(pd.DataFrame({"a": np.arange(4)}), 2)
    t2.write_parquet(p, mode="overwrite")
    assert len(glob.glob(os.path.join(p, "part-*.parquet"))) == 2
    assert FeatureTable.read_parquet(p).size() == 4
    # append continues the numbering instead of clobbering part-00000
    t2.write_parquet(p, mode="append")
    assert len(glob.glob(os.path.join(p, "part-*.parquet"))) == 4
    assert FeatureTable.read_parquet(p).size() == 8
    with pytest.raises(ValueError):
        t2.write_parquet(p, mode="errorifexists")


def test_schema_and_col_names_need_only_first_shard(monkeypatch):
    t = FeatureTable.from_pandas(pd.concat(_frames(4), ignore_index=True), 4)
    monkeypatch.setattr(
        HostXShards, "collect",
        lambda self: pytest.fail("metadata op gathered the table"))
    assert t.col_names() == ["user", "item", "cat", "hist"]
    assert "user" in t.schema.index
    assert t.size() == 64


# ------------------------------------------------------------- prefetch

def test_streaming_prefetch_depth(parallel_env):
    from analytics_zoo_tpu.data.dataset import StreamingShardedDataset
    frames = [pd.DataFrame({"f": np.arange(8) + 8 * i,
                            "label": (np.arange(8) + i) % 2})
              for i in range(6)]

    def batches(depth):
        ds = StreamingShardedDataset(HostXShards([f.copy() for f in frames]),
                                     feature_cols=["f"], label_cols="label")
        assert ds.prefetch(depth) is ds
        assert ds.prefetch_depth == depth
        return [(np.asarray(x).copy(), np.asarray(y).copy())
                for x, y, _ in ds.iter_batches(batch_size=16, shuffle=False)]

    base = batches(1)
    deep = batches(3)
    assert len(base) == len(deep) == 3
    for (x1, y1), (x2, y2) in zip(base, deep):
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)


def test_prefetch_env_default(monkeypatch):
    from analytics_zoo_tpu.data.dataset import StreamingShardedDataset
    monkeypatch.setenv("ZOO_DATA_PREFETCH", "4")
    ds = StreamingShardedDataset(
        HostXShards([pd.DataFrame({"f": [1.0], "label": [0]})]),
        feature_cols=["f"], label_cols="label")
    assert ds.prefetch_depth == 4


# ------------------------------------------- the whole chain into a fit

def test_recsys_chain_streams_into_an_ncf_fit(parallel_env, orca_ctx):
    """Raw interactions with string codes through the whole Friesian chain
    (index fit and encode, history sequences, negative samples, crossed
    columns, pad and mask, merged features), then
    ``to_streaming_dataset`` straight into ``NeuralCF``'s ``fit``: every
    row the chain made reaches the estimator, and every epoch's loss is
    a number."""
    from analytics_zoo_tpu.learn.optimizers import Adam
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    rows, users, items, seq = 1200, 50, 40, 8
    rng = np.random.default_rng(11)
    u = rng.integers(0, users, rows)
    i = rng.integers(0, items, rows)
    df = pd.DataFrame({
        "user_code": np.char.add("u", u.astype(str)),
        "item_code": np.char.add("i", i.astype(str)),
        "time": rng.integers(0, 100_000, rows),
    })
    t = FeatureTable.from_pandas(df, 4)
    indices = t.gen_string_idx(["user_code", "item_code"])
    t = t.encode_string(["user_code", "item_code"], indices)
    t = t.rename({"user_code": "user", "item_code": "item"})
    t = t.add_hist_seq("user", ["item"], sort_col="time",
                       min_len=1, max_len=seq)
    t = t.add_negative_samples(item_size=items, item_col="item", neg_num=1)
    t = t.cross_columns([["user", "item"]], [100])
    t = t.mask_pad(padding_cols=["item_hist_seq"],
                   mask_cols=["item_hist_seq"], seq_len=seq)
    t = t.add_length("item_hist_seq")
    t = t.merge_cols(["user", "item"], "features")
    assert {"features", "label", "item_hist_seq",
            "item_hist_seq_length"} <= set(t.col_names())

    ds = t.to_streaming_dataset(["features"], "label", prefetch_depth=2)
    assert ds.n == t.size() > 0
    ncf = NeuralCF(user_count=users, item_count=items, class_num=2,
                   user_embed=16, item_embed=16, hidden_layers=(32, 16),
                   include_mf=True, mf_embed=16)
    ncf.compile(optimizer=Adam(1e-3),
                loss="sparse_categorical_crossentropy")
    est = ncf.model._ensure_estimator(for_training=True)
    history = est.fit(ds, epochs=2, batch_size=128)
    assert len(history["loss"]) == 2 and np.all(np.isfinite(history["loss"]))
