"""Tests for the widened data layer: groupby/aggregates, zip, column ops,
parquet IO, push-based shuffle, preprocessors.
(reference analogs: python/ray/data/tests/test_all_to_all.py,
test_parquet.py, preprocessors/)"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data.aggregate import Count, Max, Mean, Min, Std, Sum
from ray_tpu.data.context import DataContext
from ray_tpu.data import preprocessors as pp


@pytest.fixture
def rt(ray_tpu_start):
    return ray_tpu_start


def _table(n=20):
    return rd.from_numpy({
        "k": np.arange(n) % 3,
        "x": np.arange(n, dtype=np.float64),
    })


def test_groupby_aggregates(rt):
    rows = _table(9).groupby("k").sum("x").take_all()
    # k=0: 0+3+6=9, k=1: 1+4+7=12, k=2: 2+5+8=15
    got = {int(r["k"]): r["sum(x)"] for r in rows}
    assert got == {0: 9.0, 1: 12.0, 2: 15.0}

    rows = _table(9).groupby("k").count().take_all()
    assert all(r["count"] == 3 for r in rows)

    rows = _table(9).groupby("k").mean("x").take_all()
    assert {int(r["k"]): r["mean(x)"] for r in rows} == {
        0: 3.0, 1: 4.0, 2: 5.0}


def test_groupby_multi_agg_and_std(rt):
    out = _table(10).groupby("k").aggregate(Min("x"), Max("x"),
                                            Std("x", ddof=0)).take_all()
    r0 = next(r for r in out if int(r["k"]) == 0)
    vals = np.array([0.0, 3.0, 6.0, 9.0])
    assert r0["min(x)"] == 0.0 and r0["max(x)"] == 9.0
    assert abs(r0["std(x)"] - vals.std()) < 1e-9


def test_global_aggregates(rt):
    ds = _table(10)
    assert ds.sum("x") == 45.0
    assert ds.min("x") == 0.0
    assert ds.max("x") == 9.0
    assert ds.mean("x") == 4.5
    assert abs(ds.std("x") - np.arange(10, dtype=float).std(ddof=1)) < 1e-9
    out = ds.aggregate(Count(), Sum("x"))
    assert out["count"] == 10 and out["sum(x)"] == 45.0


def test_map_groups(rt):
    out = _table(9).groupby("k").map_groups(
        lambda g: {"k": g["k"][:1], "total": np.array([g["x"].sum()])}
    ).take_all()
    assert {int(r["k"]): float(r["total"]) for r in out} == {
        0: 9.0, 1: 12.0, 2: 15.0}


def test_zip_and_column_ops(rt):
    a = rd.from_numpy({"x": np.arange(6)})
    b = rd.from_numpy({"y": np.arange(6) * 10})
    z = a.zip(b)
    rows = z.take_all()
    assert all(r["y"] == 10 * r["x"] for r in rows)

    ds = rd.from_numpy({"x": np.arange(4, dtype=np.float64)})
    ds2 = ds.add_column("sq", lambda b: b["x"] ** 2)
    assert [r["sq"] for r in ds2.take_all()] == [0.0, 1.0, 4.0, 9.0]
    assert set(ds2.select_columns(["sq"]).schema()) == {"sq"}
    assert set(ds2.drop_columns(["sq"]).schema()) == {"x"}
    assert set(ds2.rename_columns({"sq": "square"}).schema()) == {
        "x", "square"}


def test_unique_schema_split(rt):
    ds = _table(12)
    assert ds.unique("k") == [0, 1, 2]
    sch = ds.schema()
    assert sch["x"] == np.float64
    parts = ds.split(3)
    assert sum(p.count() for p in parts) == 12


def test_parquet_roundtrip(rt, tmp_path):
    ds = _table(16)
    out = str(tmp_path / "pq")
    ds.write_parquet(out)
    back = rd.read_parquet(out)
    assert back.count() == 16
    assert back.sum("x") == ds.sum("x")
    # column projection
    only_k = rd.read_parquet(out, columns=["k"])
    assert set(only_k.schema()) == {"k"}


def test_csv_json_write(rt, tmp_path):
    ds = _table(6)
    ds.write_csv(str(tmp_path / "csv"))
    ds.write_json(str(tmp_path / "json"))
    back_csv = rd.read_csv(
        [str(p) for p in sorted((tmp_path / "csv").glob("*.csv"))])
    assert back_csv.count() == 6
    back_json = rd.read_json(
        [str(p) for p in sorted((tmp_path / "json").glob("*.json"))])
    assert back_json.count() == 6
    assert sum(float(r["x"]) for r in back_json.take_all()) == 15.0


def test_push_based_shuffle(rt):
    ctx = DataContext.get_current()
    ctx.use_push_based_shuffle = True
    try:
        ds = rd.range(100).random_shuffle(seed=7)
        vals = sorted(int(r["id"]) for r in ds.take_all())
        assert vals == list(range(100))
        # actually permuted (probability of identity is ~0)
        first = [int(r["id"]) for r in
                 rd.range(100).random_shuffle(seed=7).take(10)]
        assert first != list(range(10))
    finally:
        ctx.use_push_based_shuffle = False


def test_preprocessor_standard_scaler(rt):
    ds = rd.from_numpy({"a": np.array([1.0, 2.0, 3.0, 4.0]),
                        "b": np.array([10.0, 10.0, 10.0, 10.0])})
    sc = pp.StandardScaler(["a", "b"]).fit(ds)
    out = sc.transform(ds).take_all()
    a = np.array([r["a"] for r in out])
    assert abs(a.mean()) < 1e-9 and abs(a.std() - 1.0) < 1e-9
    assert all(r["b"] == 0.0 for r in out)  # zero-variance column


def test_preprocessor_minmax_label_onehot(rt):
    ds = rd.from_items([
        {"x": 0.0, "cat": "a"}, {"x": 5.0, "cat": "b"},
        {"x": 10.0, "cat": "a"},
    ])
    mm = pp.MinMaxScaler(["x"]).fit(ds)
    xs = [r["x"] for r in mm.transform(ds).take_all()]
    assert xs == [0.0, 0.5, 1.0]

    le = pp.LabelEncoder("cat").fit(ds)
    cats = [int(r["cat"]) for r in le.transform(ds).take_all()]
    assert cats == [0, 1, 0]

    oh = pp.OneHotEncoder(["cat"]).fit(ds)
    row = oh.transform(ds).take_all()[1]
    assert row["cat_a"] == 0 and row["cat_b"] == 1


def test_preprocessor_concat_chain_batchmapper(rt):
    ds = rd.from_numpy({"f1": np.arange(4, dtype=np.float64),
                        "f2": np.arange(4, dtype=np.float64) * 2})
    chain = pp.Chain(
        pp.StandardScaler(["f1"]),
        pp.BatchMapper(lambda b: {**b, "f2": b["f2"] + 1}),
        pp.Concatenator(["f1", "f2"], "features"),
    ).fit(ds)
    out = chain.transform(ds).take_all()
    assert out[0]["features"].shape == (2,)
    # serving-time single batch path
    batch = chain.transform_batch(
        {"f1": np.array([0.0, 3.0]), "f2": np.array([1.0, 1.0])})
    assert batch["features"].shape == (2, 2)


def test_unfit_preprocessor_raises(rt):
    with pytest.raises(RuntimeError):
        pp.StandardScaler(["x"]).transform(rd.range(3))


def test_random_sample_not_positionally_biased(rt):
    ds = rd.from_numpy({"x": np.arange(80)}, num_blocks=8)
    kept = [int(r["x"]) for r in ds.random_sample(0.5, seed=1).take_all()]
    # with per-block identical masks, kept positions mod 10 would form a
    # fixed subset; distinct streams make that astronomically unlikely
    mods = {k % 10 for k in kept}
    assert len(mods) > 5
    # reproducible
    kept2 = [int(r["x"]) for r in ds.random_sample(0.5, seed=1).take_all()]
    assert kept == kept2


def test_split_exact_count_with_few_rows(rt):
    parts = rd.from_numpy({"x": np.arange(2)}).split(4)
    assert len(parts) == 4
    assert sum(p.count() for p in parts) == 2


def test_push_shuffle_reproducible(rt):
    ctx = DataContext.get_current()
    ctx.use_push_based_shuffle = True
    try:
        a = [int(r["id"]) for r in
             rd.range(60).random_shuffle(seed=5).take_all()]
        b = [int(r["id"]) for r in
             rd.range(60).random_shuffle(seed=5).take_all()]
        assert a == b
        assert sorted(a) == list(range(60))
    finally:
        ctx.use_push_based_shuffle = False


def test_zip_no_silent_overwrite(rt):
    a = rd.from_numpy({"k": np.arange(3), "k_1": np.arange(3) * 2})
    b = rd.from_numpy({"k": np.arange(3) * 5})
    cols = set(a.zip(b).schema())
    assert cols == {"k", "k_1", "k_2"}


def test_iter_torch_batches():
    import numpy as np
    import torch

    import ray_tpu.data as rdata

    ds = rdata.range(100, num_blocks=4).map_batches(
        lambda b: {"x": np.asarray(b["id"], np.float32) * 2})
    batches = list(ds.iterator().iter_torch_batches(batch_size=32))
    assert all(isinstance(b["x"], torch.Tensor) for b in batches)
    total = torch.cat([b["x"] for b in batches])
    assert total.shape == (100,)
    assert float(total.sum()) == float(2 * sum(range(100)))


def test_from_pandas_and_to_rows():
    import pandas as pd

    import ray_tpu.data as rdata

    df = pd.DataFrame({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    ds = rdata.from_pandas(df, num_blocks=2)
    rows = ds.take_all()
    assert [r["a"] for r in rows] == [1, 2, 3]
    assert [r["b"] for r in rows] == ["x", "y", "z"]


def test_read_text_and_binary(tmp_path):
    import ray_tpu.data as rdata

    p1 = tmp_path / "a.txt"
    p1.write_text("hello\nworld\n\nlast\n")
    ds = rdata.read_text(str(p1))
    assert [r["text"] for r in ds.take_all()] == ["hello", "world", "last"]

    p2 = tmp_path / "blob.bin"
    p2.write_bytes(b"\x00\x01\x02")
    ds2 = rdata.read_binary_files(str(p2), include_paths=True)
    row = ds2.take_all()[0]
    assert row["bytes"] == b"\x00\x01\x02" and row["path"].endswith("blob.bin")


def test_to_pandas_roundtrip():
    import pandas as pd

    import ray_tpu.data as rdata

    df = pd.DataFrame({"a": [1, 2, 3], "b": [4.0, 5.0, 6.0]})
    out = rdata.from_pandas(df).to_pandas()
    pd.testing.assert_frame_equal(
        out.sort_values("a").reset_index(drop=True), df)


def test_to_pandas_multidim_column():
    import numpy as np

    import ray_tpu.data as rdata

    ds = rdata.from_numpy({"emb": np.arange(8.0).reshape(4, 2)})
    df = ds.to_pandas()
    assert len(df) == 4
    assert list(df["emb"].iloc[0]) == [0.0, 1.0]


def test_arrow_interop_roundtrip(ray_tpu_start):
    """from_arrow -> transforms -> to_arrow (reference: Arrow-native
    blocks + from_arrow/to_arrow surface)."""
    import pyarrow as pa

    from ray_tpu import data as rdata

    table = pa.table({"x": list(range(10)), "y": [f"r{i}" for i in range(10)]})
    ds = rdata.from_arrow(table)
    out = ds.map_batches(lambda b: {"x2": b["x"] * 2}).to_arrow()
    assert isinstance(out, pa.Table)
    assert sorted(out.column("x2").to_pylist()) == [2 * i for i in range(10)]


def test_read_parquet_file_uri(tmp_path, ray_tpu_start):
    """pyarrow.fs URI paths resolve (file:// here; s3://, gs:// share the
    same code path with credentials)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ray_tpu import data as rdata

    p = tmp_path / "t.parquet"
    pq.write_table(pa.table({"a": [1, 2, 3]}), p)
    rows = rdata.read_parquet(f"file://{p}").take_all()
    assert sorted(r["a"] for r in rows) == [1, 2, 3]


def test_read_csv_and_text_file_uri(tmp_path, ray_tpu_start):
    from ray_tpu import data as rdata

    csv = tmp_path / "t.csv"
    csv.write_text("a,b\n1,x\n2,y\n")
    rows = rdata.read_csv(f"file://{csv}").take_all()
    assert len(rows) == 2 and rows[0]["b"] in ("x", "y")

    txt = tmp_path / "t.txt"
    txt.write_text("hello\nworld\n")
    rows = rdata.read_text(f"file://{txt}").take_all()
    assert sorted(r["text"] for r in rows) == ["hello", "world"]


def test_actor_pool_autoscales(ray_tpu_start):
    """Actor-pool map scales up under queued work and back down when the
    input drains (reference: ActorPoolMapOperator autoscaling)."""
    import time

    from ray_tpu import data as rdata
    from ray_tpu.data.execution import MapOperator

    captured = []
    orig_init = MapOperator.__init__

    def spy_init(self, *a, **k):
        orig_init(self, *a, **k)
        captured.append(self)

    MapOperator.__init__ = spy_init
    try:
        def slowish(batch):
            time.sleep(0.05)
            return batch

        rows = (rdata.range(400)
                .map_batches(lambda: slowish, compute="actors",
                             actor_pool_size=1, max_actor_pool_size=4)
                .take_all())
        assert len(rows) == 400
        op = next(o for o in captured if o.name == "MapBatches")
        assert op.metrics.get("actors_started", 0) >= 2, op.metrics
        assert len(op._pool) == 0, "idle actors not retired after drain"
    finally:
        MapOperator.__init__ = orig_init


# ---------------------------------------------------------------------------
# round-3 additions: DatasetStats + TFRecord + WebDataset
# ---------------------------------------------------------------------------

def test_dataset_stats(ray_tpu_start):
    import ray_tpu.data as rdata

    ds = rdata.range(32, num_blocks=4).map_batches(
        lambda b: {"id": b["id"] * 2})
    ds.take_all()
    out = ds.stats()
    assert "Operator 0 Input" in out
    assert "rows" in out and "task wall" in out
    assert out["MapBatches"]["tasks"] == 4
    assert out["MapBatches"]["rows_out"] == 32
    # an unexecuted dataset executes once to produce stats
    fresh = rdata.range(4, num_blocks=2).map_batches(lambda b: b)
    assert fresh.stats()["MapBatches"]["tasks"] == 2


def test_tfrecord_roundtrip(ray_tpu_start, tmp_path):
    import ray_tpu.data as rdata

    rows = [
        {"label": 3, "name": "cat", "scores": [0.5, 1.5]},
        {"label": 7, "name": "dog", "scores": [2.0]},
        {"label": 1, "name": b"raw-bytes", "scores": [0.0, -1.0, 4.0]},
    ]
    path = str(tmp_path / "data.tfrecord")
    rdata.write_tfrecords_file(rows, path)
    back = rdata.read_tfrecords(path).take_all()
    assert len(back) == 3
    assert back[0]["label"] == 3
    assert back[0]["name"] == b"cat"         # bytes feature (TF semantics)
    assert back[0]["scores"] == [0.5, 1.5]
    assert back[1]["scores"] == 2.0          # single element unwraps
    assert back[2]["name"] == b"raw-bytes"


def test_tfrecord_crc_detects_corruption(tmp_path):
    from ray_tpu.data import tfrecord as tfr

    framed = bytearray(tfr.frame_record(tfr.build_example({"x": 1})))
    framed[14] ^= 0xFF    # flip a payload byte
    import pytest

    with pytest.raises(ValueError, match="CRC"):
        list(tfr.iter_records(bytes(framed)))


@pytest.mark.parametrize("data,want", [
    (b"", 0x00000000),
    (b"123456789", 0xE3069283),            # the Castagnoli check value
    (bytes(32), 0x8A9136AA),               # RFC 3720 B.4: 32 zero bytes
    (bytes([0xFF] * 32), 0x62A8AB43),      # RFC 3720 B.4: 32 bytes of 0xFF
    (bytes(range(32)), 0x46DD794E),        # RFC 3720 B.4: ascending bytes
])
def test_crc32c_known_vectors(data, want):
    """The native CRC32C (the only implementation: there is no Python
    fallback to agree with) against published vectors."""
    from ray_tpu.data import tfrecord as tfr

    assert tfr._crc32c(data) == want


def test_webdataset_reader(ray_tpu_start, tmp_path):
    import io
    import tarfile

    import ray_tpu.data as rdata

    tar_path = tmp_path / "shard-000.tar"
    with tarfile.open(tar_path, "w") as tar:
        for key, cls, txt in (("s1", 0, "hello"), ("s2", 4, "world")):
            for ext, payload in (("cls", str(cls).encode()),
                                 ("txt", txt.encode()),
                                 ("bin", b"\x00\x01")):
                data = io.BytesIO(payload)
                info = tarfile.TarInfo(f"{key}.{ext}")
                info.size = len(payload)
                tar.addfile(info, data)
    rows = rdata.read_webdataset(str(tar_path)).take_all()
    assert len(rows) == 2
    by_key = {r["__key__"]: r for r in rows}
    assert by_key["s1"]["cls"] == 0 and by_key["s1"]["txt"] == "hello"
    assert by_key["s2"]["cls"] == 4 and by_key["s2"]["bin"] == b"\x00\x01"
