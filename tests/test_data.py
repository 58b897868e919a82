"""Data library tests (reference patterns: python/ray/data/tests/)."""

import builtins
import time

import numpy as np
import pytest

import ray_tpu
from conftest import _CLUSTER_SUSPECT
from ray_tpu import data as rd


@pytest.fixture(autouse=True)
def _cpus_come_back(request):
    """The file's tests share one 4-CPU cluster, and a test here needs up to
    all four (four read tasks, a pool of two actors). A CPU that an earlier
    test's lease or killed actor still holds starves it: seen beside six busy
    workers after ``test_map_batches_actor_pool_stateful`` (3 of 4 free for the
    rest of the file; ROADMAP D2 has the readings), and the next pool actor
    then waits out its whole limit for an address. So a test hands the cluster
    on only with every CPU back, and one that does not within the bound has the
    next test boot its own (``ray_cluster`` does that for a suspect cluster)."""
    yield
    if not ray_tpu.is_initialized():
        return
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if (ray_tpu.available_resources().get("CPU", 0.0)
                >= ray_tpu.cluster_resources().get("CPU", 0.0)):
            return
        time.sleep(0.05)
    request.config.stash[_CLUSTER_SUSPECT] = True


def test_range_count_take(ray_cluster):
    ds = rd.range(100, parallelism=4)
    assert ds.count() == 100
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_map_batches_fusion(ray_cluster):
    ds = (
        rd.range(64, parallelism=4)
        .map_batches(lambda b: {"id": b["id"] * 2})
        .map_batches(lambda b: {"id": b["id"] + 1})
    )
    from ray_tpu.data.executor import plan

    ops = plan(ds._last_op)
    assert len(ops) == 2  # Read + one fused Map
    out = sorted(r["id"] for r in ds.take_all())
    assert out == sorted((i * 2) + 1 for i in range(64))


def test_map_filter_flat_map(ray_cluster):
    ds = rd.from_items([{"x": i} for i in range(10)], parallelism=2)
    out = (
        ds.map(lambda r: {"x": r["x"] * 10})
        .filter(lambda r: r["x"] >= 50)
        .flat_map(lambda r: [{"x": r["x"]}, {"x": r["x"] + 1}])
    )
    vals = sorted(r["x"] for r in out.take_all())
    assert vals == sorted(v for i in range(5, 10) for v in (i * 10, i * 10 + 1))


def test_repartition_and_shuffle(ray_cluster):
    ds = rd.range(50, parallelism=5).repartition(3)
    mat = ds.materialize()
    assert mat.num_blocks() == 3
    assert mat.count() == 50

    shuffled = rd.range(50, parallelism=5).random_shuffle(seed=0)
    vals = [r["id"] for r in shuffled.take_all()]
    assert sorted(vals) == list(range(50))
    assert vals != list(range(50))


def test_sort(ray_cluster):
    ds = rd.from_items([{"v": i % 7, "i": i} for i in range(30)], parallelism=3)
    out = [r["v"] for r in ds.sort("v").take_all()]
    assert out == sorted(out)
    out_desc = [r["v"] for r in ds.sort("v", descending=True).take_all()]
    assert out_desc == sorted(out, reverse=True)


def test_limit_streaming(ray_cluster):
    ds = rd.range(1000, parallelism=10).limit(17)
    assert ds.count() == 17


def test_iter_batches_sizes(ray_cluster):
    ds = rd.range(100, parallelism=4)
    batches = list(ds.iter_batches(batch_size=32))
    sizes = [len(b["id"]) for b in batches]
    assert sum(sizes) == 100
    assert sizes[:-1] == [32, 32, 32]
    b0 = batches[0]
    assert isinstance(b0["id"], np.ndarray)


def test_tensor_columns_roundtrip(ray_cluster):
    arr = np.arange(60, dtype=np.float32).reshape(20, 3)
    ds = rd.from_numpy(arr, column="feat")
    batch = next(iter(ds.iter_batches(batch_size=None)))
    np.testing.assert_array_equal(batch["feat"], arr)
    out = ds.map_batches(lambda b: {"feat": b["feat"] * 2.0}).take_all()
    np.testing.assert_allclose(out[0]["feat"], arr[0] * 2.0)


def test_parquet_roundtrip(ray_cluster, tmp_path):
    ds = rd.range(40, parallelism=2)
    ds.write_parquet(str(tmp_path / "pq"))
    back = rd.read_parquet(str(tmp_path / "pq"))
    assert back.count() == 40
    assert sorted(r["id"] for r in back.take_all()) == list(range(40))


def test_streaming_split_feeds_all_consumers(ray_cluster):
    ds = rd.range(60, parallelism=6)
    its = ds.streaming_split(2)
    seen = []
    for it in its:
        for batch in it.iter_batches(batch_size=None):
            seen.extend(batch["id"].tolist())
    assert sorted(seen) == list(range(60))


def test_map_batches_actor_pool_stateful(ray_cluster, monkeypatch):
    """A class fn is constructed once per pool actor (the inference
    pattern); results are correct and block order is preserved."""
    from ray_tpu.core.config import get_config
    from ray_tpu.data import ActorPoolStrategy

    # a pool actor that gets no address fails the test in 60 s, not 120
    monkeypatch.setattr(get_config(), "actor_resolve_timeout_s", 60.0)

    class AddModel:
        def __init__(self, offset):
            import os

            self.offset = offset
            self.pid = os.getpid()

        def __call__(self, batch):
            return {"id": batch["id"] + self.offset, "pid": np.full(len(batch["id"]), self.pid)}

    ds = rd.range(40, parallelism=4)
    out = ds.map_batches(
        AddModel, compute=ActorPoolStrategy(size=2), fn_constructor_args=(100,)
    ).take_all()
    assert sorted(r["id"] for r in out) == list(builtins.range(100, 140))
    # constructed per-actor, not per-block: at most pool-size distinct pids
    assert len({r["pid"] for r in out}) <= 2


def test_read_text_and_binary(ray_cluster, tmp_path):
    (tmp_path / "a.txt").write_text("alpha\nbeta\n")
    (tmp_path / "b.txt").write_text("gamma\n")
    ds = rd.read_text([str(tmp_path / "a.txt"), str(tmp_path / "b.txt")])
    assert sorted(r["text"] for r in ds.take_all()) == ["alpha", "beta", "gamma"]

    (tmp_path / "blob.bin").write_bytes(b"\x00\x01\x02")
    rows = rd.read_binary_files(str(tmp_path / "blob.bin")).take_all()
    assert rows[0]["bytes"] == b"\x00\x01\x02"


def test_union_and_write_json(ray_cluster, tmp_path):
    import json

    a = rd.range(5, parallelism=1)
    b = rd.range(5, parallelism=1).map(lambda r: {"id": r["id"] + 10})
    u = a.union(b)
    assert sorted(r["id"] for r in u.take_all()) == [0, 1, 2, 3, 4, 10, 11, 12, 13, 14]

    u.write_json(str(tmp_path / "out"))
    rows = []
    for f in sorted((tmp_path / "out").iterdir()):
        rows += [json.loads(line) for line in f.read_text().splitlines()]
    assert sorted(r["id"] for r in rows) == [0, 1, 2, 3, 4, 10, 11, 12, 13, 14]


def test_groupby_aggregations(ray_cluster):
    """groupby().count/sum/min/max/mean through the hash exchange with
    map-side partial aggregation (reference grouped_data.py:21)."""
    rows = [{"k": i % 3, "v": float(i)} for i in range(30)]
    ds = rd.from_items(rows, parallelism=4)

    counts = {r["k"]: r["count()"] for r in ds.groupby("k").count().take_all()}
    assert counts == {0: 10, 1: 10, 2: 10}

    sums = {r["k"]: r["sum(v)"] for r in ds.groupby("k").sum("v").take_all()}
    assert sums == {k: sum(float(i) for i in range(30) if i % 3 == k) for k in range(3)}

    mins = {r["k"]: r["min(v)"] for r in ds.groupby("k").min("v").take_all()}
    assert mins == {0: 0.0, 1: 1.0, 2: 2.0}

    maxs = {r["k"]: r["max(v)"] for r in ds.groupby("k").max("v").take_all()}
    assert maxs == {0: 27.0, 1: 28.0, 2: 29.0}

    means = {r["k"]: r["mean(v)"] for r in ds.groupby("k").mean("v").take_all()}
    assert means == {k: sums[k] / 10 for k in range(3)}

    multi = ds.groupby("k").aggregate(("v", "sum"), ("v", "max")).take_all()
    assert {r["k"]: (r["sum(v)"], r["max(v)"]) for r in multi} == {
        k: (sums[k], maxs[k]) for k in range(3)}


def test_groupby_map_groups(ray_cluster):
    ds = rd.from_items([{"k": i % 2, "v": i} for i in range(10)], parallelism=3)

    def normalize(batch):
        v = batch["v"]
        return {"k": batch["k"][:1], "spread": [int(v.max() - v.min())]}

    out = ds.groupby("k").map_groups(normalize).take_all()
    assert sorted((r["k"], r["spread"]) for r in out) == [(0, 8), (1, 8)]


def test_join_inner_and_left(ray_cluster):
    left = rd.from_items([{"id": i, "a": i * 10} for i in range(8)], parallelism=3)
    right = rd.from_items([{"id": i, "b": i * 100} for i in range(0, 8, 2)], parallelism=2)

    inner = left.join(right, on="id").take_all()
    assert sorted((r["id"], r["a"], r["b"]) for r in inner) == [
        (i, i * 10, i * 100) for i in range(0, 8, 2)]

    outer = left.join(right, on="id", how="left outer").take_all()
    assert len(outer) == 8
    matched = {r["id"]: r["b"] for r in outer if r["b"] is not None}
    assert matched == {i: i * 100 for i in range(0, 8, 2)}


def test_zip(ray_cluster):
    a = rd.from_items([{"x": i} for i in range(12)], parallelism=3)
    b = rd.from_items([{"y": i * 2} for i in range(12)], parallelism=4)  # misaligned blocks
    out = a.zip(b).take_all()
    assert sorted((r["x"], r["y"]) for r in out) == [(i, i * 2) for i in range(12)]

    with pytest.raises(ValueError, match="equal row counts"):
        a.zip(rd.from_items([{"y": 1}], parallelism=1)).take_all()


def test_shuffle_exchange_is_partitioned(ray_cluster):
    """random_shuffle runs as a map-reduce exchange: output arrives as
    multiple partition blocks (not one consolidation block), preserves the
    multiset, and actually permutes."""
    ds = rd.range(2000, parallelism=8).random_shuffle(seed=7)
    refs = list(ds.iter_internal_ref_bundles())
    assert len(refs) > 1, "shuffle must emit one block per partition"
    rows = [r["id"] for r in ds.iter_rows()]
    assert sorted(rows) == list(builtins.range(2000))
    assert rows != sorted(rows)


def test_sort_exchange_range_partitioned(ray_cluster):
    """sort samples boundaries and range-partitions; the global stream is
    ordered across partition blocks."""
    import random

    vals = list(builtins.range(500))
    random.Random(3).shuffle(vals)
    ds = rd.from_items([{"v": v} for v in vals], parallelism=6).sort("v")
    refs = list(ds.iter_internal_ref_bundles())
    assert len(refs) > 1
    out = [r["v"] for r in ds.iter_rows()]
    assert out == sorted(vals)


def test_sort_string_keys(ray_cluster):
    """Range boundaries come from order statistics, so non-numeric (string)
    sort keys partition correctly (regression: np.quantile TypeError)."""
    import random

    words = [f"w{i:03d}" for i in builtins.range(120)]
    shuffled = list(words)
    random.Random(11).shuffle(shuffled)
    ds = rd.from_items([{"s": w} for w in shuffled], parallelism=5).sort("s")
    out = [r["s"] for r in ds.iter_rows()]
    assert out == sorted(words)
    out_desc = [r["s"] for r in rd.from_items(
        [{"s": w} for w in shuffled], parallelism=5).sort("s", descending=True).iter_rows()]
    assert out_desc == sorted(words, reverse=True)


def test_join_empty_left_side(ray_cluster):
    """A join whose left upstream produced zero blocks must not crash the
    reduce tasks (regression: _concat_keep_schema IndexError)."""
    left = rd.from_items([], parallelism=1)
    right = rd.from_items([{"id": i, "b": i} for i in builtins.range(6)], parallelism=2)
    out = left.join(right, on="id").take_all()
    assert out == []


def test_parquet_row_group_streaming_tasks(ray_cluster, tmp_path):
    """A parquet file with many row groups splits into row-group-granular
    read tasks (bounded memory for larger-than-RAM datasets) and streams
    the right rows through streaming_split consumers."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = tmp_path / "big"
    path.mkdir()
    n = 20_000
    table = pa.table({"x": np.arange(n, dtype=np.int64)})
    pq.write_table(table, str(path / "data.parquet"), row_group_size=1000)  # 20 groups

    ds = rd.read_parquet(str(path), row_groups_per_task=2)
    assert len(ds._last_op.read_tasks) == 10, "expected one task per 2 row groups"

    seen = []
    its = ds.streaming_split(2)

    def consume(it):
        for b in it.iter_batches(batch_size=4096):
            seen.extend(b["x"].tolist())

    import threading

    threads = [threading.Thread(target=consume, args=(it,)) for it in its]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert sorted(seen) == list(builtins.range(n))


def test_filesystem_uri_roundtrip(ray_cluster, tmp_path):
    """file:// URIs resolve through pyarrow.fs — the same code path as
    gs:// / s3:// buckets (zero-egress env: local fs stands in)."""
    uri = "file://" + str(tmp_path / "out")
    rd.range(100, parallelism=2).write_parquet(uri)
    back = rd.read_parquet(uri)
    assert back.count() == 100
    assert sorted(r["id"] for r in back.take_all()) == list(builtins.range(100))

    rd.from_items([{"a": 1}, {"a": 2}]).write_json("file://" + str(tmp_path / "j"))
    assert sorted(r["a"] for r in rd.read_json(
        "file://" + str(tmp_path / "j")).take_all()) == [1, 2]


def test_read_images(ray_cluster, tmp_path):
    from PIL import Image

    d = tmp_path / "imgs"
    d.mkdir()
    for i in builtins.range(5):
        arr = np.full((8, 6, 3), i * 10, np.uint8)
        Image.fromarray(arr).save(str(d / f"im{i}.png"))
    ds = rd.read_images(str(d), size=(4, 4), mode="RGB")
    rows = ds.take_all()
    assert len(rows) == 5
    imgs = sorted(rows, key=lambda r: r["path"])
    assert np.asarray(imgs[0]["image"]).shape == (4, 4, 3)
    assert int(np.asarray(imgs[3]["image"]).mean()) == 30


def test_preprocessors_scalers_encoders_chain(ray_cluster):
    """Preprocessor contract (fit -> transform -> transform_batch) and
    the standard library: scalers, encoders, imputer, concatenator,
    chain (reference python/ray/data/preprocessors/)."""
    import numpy as np
    import pytest as _pytest

    from ray_tpu import data
    from ray_tpu.data.preprocessors import (
        Chain, Concatenator, LabelEncoder, MinMaxScaler, OneHotEncoder,
        PreprocessorNotFittedError, SimpleImputer, StandardScaler)

    rows = [{"x": float(i), "y": float(i * 2), "cat": ["a", "b", "c"][i % 3],
             "label": ["pos", "neg"][i % 2]} for i in range(30)]
    ds = data.from_items(rows)

    with _pytest.raises(PreprocessorNotFittedError):
        StandardScaler(["x"]).transform(ds)

    # StandardScaler: mean ~0 std ~1
    sc = StandardScaler(["x", "y"]).fit(ds)
    out = sc.transform(ds).take_all()
    xs = np.asarray([r["x"] for r in out])
    assert abs(xs.mean()) < 1e-6 and abs(xs.std() - 1.0) < 1e-6

    # MinMaxScaler: [0, 1]
    mm = MinMaxScaler(["x"]).fit(ds)
    out = mm.transform(ds).take_all()
    xs = [r["x"] for r in out]
    assert min(xs) == 0.0 and max(xs) == 1.0

    # LabelEncoder: ints + inverse; unseen label raises
    le = LabelEncoder("label").fit(ds)
    out = le.transform(ds).take_all()
    assert {r["label"] for r in out} == {0, 1}
    back = le.inverse_transform_batch({"label": np.asarray([0, 1])})
    assert set(back["label"].tolist()) == {"neg", "pos"}
    with _pytest.raises(ValueError, match="not seen"):
        le.transform_batch({"label": np.asarray(["mystery"])})

    # OneHotEncoder: per-value 0/1 columns, source dropped, unseen -> zeros
    oh = OneHotEncoder(["cat"]).fit(ds)
    b = oh.transform_batch({"cat": np.asarray(["a", "zz"])})
    assert "cat" not in b
    assert b["cat_a"].tolist() == [1, 0]
    assert b["cat_b"].tolist() == [0, 0] and b["cat_c"].tolist() == [0, 0]

    # SimpleImputer: mean fill
    ds_nan = data.from_items([{"v": 1.0}, {"v": float("nan")}, {"v": 3.0}])
    imp = SimpleImputer(["v"]).fit(ds_nan)
    vals = sorted(r["v"] for r in imp.transform(ds_nan).take_all())
    assert vals == [1.0, 2.0, 3.0]

    # Concatenator: 2-D feature column
    cat = Concatenator(columns=["x", "y"], output_column_name="features")
    b = cat.transform_batch({"x": np.asarray([1.0, 2.0]),
                             "y": np.asarray([3.0, 4.0])})
    assert b["features"].shape == (2, 2)

    # Chain: scale -> encode -> concat, fit end-to-end, batch path too
    chain = Chain(StandardScaler(["x"]), LabelEncoder("label"),
                  Concatenator(columns=["x", "y"], output_column_name="f"))
    out = chain.fit_transform(ds).take_all()
    assert set(out[0]) == {"cat", "label", "f"}
    b = chain.transform_batch({"x": np.asarray([0.0]), "y": np.asarray([1.0]),
                               "cat": np.asarray(["a"]),
                               "label": np.asarray(["pos"])})
    assert b["f"].shape == (1, 2) and b["label"].tolist() == [1]


# ------------------------------------------------------- tfrecords / hf / stats

def test_tfrecords_roundtrip(ray_cluster, tmp_path):
    """Write tf.train.Example shards with the native codec, read them
    back through the streaming executor (reference
    tfrecords_datasource.py; no TensorFlow import)."""
    from ray_tpu import data

    rows = [{"idx": i, "name": f"row-{i}", "vec": [float(i), i + 0.5],
             "blob": bytes([i, i + 1])} for i in range(10)]
    ds1 = data.from_items(rows, parallelism=3)
    ds1.write_tfrecords(str(tmp_path))
    import glob
    shards = sorted(glob.glob(str(tmp_path / "*.tfrecords")))
    assert len(shards) >= 1

    back = data.read_tfrecords(str(tmp_path)).take_all()
    back.sort(key=lambda r: r["idx"])
    for orig, got in zip(rows, back):
        assert got["idx"] == orig["idx"]
        assert got["name"] == orig["name"].encode()  # bytes feature
        assert got["blob"] == orig["blob"]
        assert [round(v, 4) for v in got["vec"]] == orig["vec"]


def test_webdataset_roundtrip(ray_cluster, tmp_path):
    """Write tar shards in the webdataset layout (one member per column
    per row, grouped by stem), read them back through the streaming
    executor (reference webdataset_datasource.py; ROADMAP item 8)."""
    from ray_tpu import data

    rows = [{"cls": i, "txt": f"caption {i}", "json": {"i": i, "tag": "x"},
             "bin": bytes([i, 255 - i])} for i in range(10)]
    ds1 = data.from_items(rows, parallelism=3)
    ds1.write_webdataset(str(tmp_path))
    import glob
    shards = sorted(glob.glob(str(tmp_path / "*.tar")))
    assert len(shards) >= 1
    # shards are REAL tar files any webdataset consumer can open
    import tarfile
    with tarfile.open(shards[0]) as tf:
        names = tf.getnames()
    assert any(n.endswith(".txt") for n in names)

    back = data.read_webdataset(str(tmp_path)).take_all()
    back.sort(key=lambda r: r["cls"])
    for orig, got in zip(rows, back):
        assert got["cls"] == orig["cls"]          # int-decoded extension
        assert got["txt"] == orig["txt"]          # text-decoded
        assert got["json"] == orig["json"]        # parsed json
        assert got["bin"] == orig["bin"]          # raw bytes
        assert got["__key__"]                      # sample stem column


def test_webdataset_sample_grouping_and_key():
    """Members group into samples by stem in stream order; an explicit
    __key__ column round-trips as member basenames."""
    import io
    import tarfile

    from ray_tpu.data import webdataset as wds

    buf = io.BytesIO()
    wds.write_shard(buf, [{"__key__": "s/a", "txt": "one", "cls": 1},
                          {"__key__": "s/b", "txt": "two", "cls": 2}])
    buf.seek(0)
    with tarfile.open(fileobj=buf) as tf:
        assert sorted(tf.getnames()) == [
            "s/a.cls", "s/a.txt", "s/b.cls", "s/b.txt"]
    buf.seek(0)
    samples = wds.iter_samples(buf)
    assert samples == [{"__key__": "s/a", "txt": "one", "cls": 1},
                       {"__key__": "s/b", "txt": "two", "cls": 2}]


def test_tfrecords_interop_with_tensorflow_writer(tmp_path):
    """Cross-check the native TFRecord framing + Example codec against a
    record written byte-for-byte by the spec (masked crc32c vectors)."""
    from ray_tpu.data import tfrecords as tfr

    # crc32c known-answer test (Castagnoli): crc32c(b"123456789")
    assert tfr.crc32c(b"123456789") == 0xE3069283
    payload = tfr.encode_example({"a": 1, "b": "x"})
    import io

    buf = io.BytesIO()
    tfr.write_record(buf, payload)
    buf.seek(0)
    records = list(tfr.read_records(buf))
    assert records == [payload]
    assert tfr.parse_example(payload) == {"a": 1, "b": b"x"}


def test_from_huggingface_and_stats(ray_cluster):
    from ray_tpu import data
    import pyarrow as pa

    # duck-typed HF dataset: .data exposes the arrow table
    class FakeHF:
        def __init__(self, table):
            self.data = table

    table = pa.table({"x": list(range(100)), "y": [i * 2 for i in range(100)]})
    ds1 = data.from_huggingface(FakeHF(table), parallelism=4)
    out = ds1.map_batches(lambda b: {"z": b["x"] + b["y"]}).take_all()
    assert [r["z"] for r in out] == [i * 3 for i in range(100)]

    # per-op stats surfaced after execution (reference _internal/stats.py)
    ds2 = data.from_huggingface(table, parallelism=4).map_batches(
        lambda b: {"x2": b["x"] * 2})
    ds2.take_all()
    report = ds2.stats()
    assert "Read" in report and "tasks" in report and "wall" in report


def test_avro_roundtrip(ray_cluster, tmp_path):
    """Write Avro Object Container File shards with the native codec,
    read them back through the streaming executor (reference
    read_api.read_avro; ROADMAP item 8, closing the readers backlog)."""
    import glob

    from ray_tpu import data

    rows = [{"id": i, "score": i * 0.5, "name": f"row {i}",
             "blob": bytes([i, 7]), "flag": i % 2 == 0,
             "vec": [i, i + 1, i + 2],
             "maybe": None if i % 3 == 0 else f"v{i}"}
            for i in range(20)]
    ds1 = data.from_items(rows, parallelism=3)
    ds1.write_avro(str(tmp_path))
    shards = sorted(glob.glob(str(tmp_path / "*.avro")))
    assert len(shards) >= 1
    # shards carry the spec'd container magic + self-describing schema
    with open(shards[0], "rb") as f:
        head = f.read(256)
    assert head.startswith(b"Obj\x01") and b"avro.schema" in head

    back = data.read_avro(str(tmp_path)).take_all()
    back.sort(key=lambda r: r["id"])
    assert len(back) == len(rows)
    for orig, got in zip(rows, back):
        assert got["id"] == orig["id"]
        assert got["score"] == orig["score"]
        assert got["name"] == orig["name"]
        assert got["blob"] == orig["blob"]
        assert got["flag"] == orig["flag"]
        assert list(got["vec"]) == orig["vec"]
        assert got["maybe"] == orig["maybe"]          # nullable union


def test_avro_codec_units():
    """Container-level invariants: zig-zag longs, schema inference
    (nullable unions, arrays, long+double merge), sync-marker check, and
    numpy normalization."""
    import io

    import numpy as np
    import pytest

    from ray_tpu.data import avro

    # zig-zag longs round-trip across the signed range
    for v in (0, -1, 1, 63, -64, 2**40, -(2**40)):
        buf = bytearray()
        avro._write_long(buf, v)
        assert avro._read_long(io.BytesIO(bytes(buf))) == v

    schema = avro.infer_schema([
        {"a": 1, "b": [1.5], "c": None}, {"a": 2.5, "b": [], "c": "x"}])
    by_name = {f["name"]: f["type"] for f in schema["fields"]}
    assert by_name["a"] == "double"                     # long+double merge
    assert by_name["b"] == {"type": "array", "items": "double"}
    assert by_name["c"] == ["null", "string"]

    # numpy arrays/scalars normalize through tolist
    buf = io.BytesIO()
    avro.write_container(buf, [{"x": np.int64(3), "y": np.arange(4)}])
    buf.seek(0)
    (row,) = avro.read_container(buf)
    assert row == {"x": 3, "y": [0, 1, 2, 3]}

    # corrupt sync marker fails loudly, not with garbage rows
    data_bytes = bytearray(buf.getvalue())
    data_bytes[-1] ^= 0xFF
    with pytest.raises(ValueError, match="sync"):
        avro.read_container(io.BytesIO(bytes(data_bytes)))
