"""Data pipeline tests: shard download/write and rank-strided loading
(mirrors reference test_loaders.py behaviors)."""

from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from penroz_tpu.data import loaders


@pytest.fixture
def shard_dir(workdir):
    (workdir / "data").mkdir(exist_ok=True)
    return workdir / "data"


def _write_shards(shard_dir, dataset_id, sizes):
    for i, size in enumerate(sizes):
        np.save(shard_dir / f"{dataset_id}_{i:06d}",
                np.arange(size, dtype=np.uint16) + 100 * i)


def test_loader_list_and_delete(shard_dir):
    _write_shards(shard_dir, "ds", [10, 10])
    loader = loaders.Loader("ds")
    assert loader.list() == ["ds_000000.npy", "ds_000001.npy"]
    loader.delete()
    assert loaders.Loader("ds").list() == []


def test_next_batch_shapes_and_shift(shard_dir):
    _write_shards(shard_dir, "ds", [100])
    loader = loaders.Loader("ds", begin_shard=0, begin_idx=0, buffer_size=8,
                            idx_offset=8)
    x, y = loader.next_batch()
    assert x.dtype == np.int32 and len(x) == 8
    np.testing.assert_array_equal(y, x + 1)  # arange data: shift-by-1 target
    x2, _ = loader.next_batch()
    assert x2[0] == 8  # advanced by idx_offset


def test_next_batch_rank_striding(shard_dir):
    _write_shards(shard_dir, "ds", [1000])
    # rank 1 of 2: begins at buffer_size, strides 2*buffer_size
    loader = loaders.Loader("ds", begin_idx=8, buffer_size=8, idx_offset=16)
    x, _ = loader.next_batch()
    assert x[0] == 8
    x2, _ = loader.next_batch()
    assert x2[0] == 24


def test_shard_wraparound(shard_dir):
    _write_shards(shard_dir, "ds", [10, 10])
    loader = loaders.Loader("ds", buffer_size=8, idx_offset=8)
    seen = [loader.next_batch()[0] for _ in range(4)]
    # 2 shards of 10 tokens: the loader must wrap 0 → 1 → 0 without gaps
    assert all(len(s) == 8 for s in seen)
    assert seen[0][0] == 0 and seen[1][0] == 8


def test_target_offset_zero_returns_none_target(shard_dir):
    _write_shards(shard_dir, "ds", [50])
    loader = loaders.Loader("ds", buffer_size=8, idx_offset=8)
    x = loader.next_batch(target_offset=0)
    assert x[1] is None


def test_downloader_writes_fixed_size_shards(shard_dir, monkeypatch):
    monkeypatch.setattr(loaders, "DATA_FOLDER", str(shard_dir))
    fake_tokenizer = MagicMock()
    fake_tokenizer.tokenize.side_effect = lambda text: [1, 2, 3]
    with patch.object(loaders, "Tokenizer", return_value=fake_tokenizer):
        downloader = loaders.Downloader("dl", shard_size=5, encoding="byte")
    fake_ds = {"text": ["a"] * 4}  # 12 tokens → shards of 5,5,2
    import sys
    fake_datasets = MagicMock()
    fake_datasets.load_dataset.return_value = fake_ds
    monkeypatch.setitem(sys.modules, "datasets", fake_datasets)
    downloader.download("path", "name", "train")
    files = sorted(f.name for f in shard_dir.glob("dl_*.npy"))
    assert files == ["dl_000000.npy", "dl_000001.npy", "dl_000002.npy"]
    assert len(np.load(shard_dir / "dl_000000.npy")) == 5
    assert len(np.load(shard_dir / "dl_000002.npy")) == 2
    assert np.load(shard_dir / "dl_000000.npy").dtype == np.uint16


def test_loader_ignores_other_datasets(shard_dir):
    _write_shards(shard_dir, "aaa", [10])
    _write_shards(shard_dir, "bbb", [10])
    assert loaders.Loader("aaa").list() == ["aaa_000000.npy"]


# -- native mmap stream -----------------------------------------------------

def _make_shards(tmp_path, sizes, dataset="nat"):
    import numpy as np, os
    data_dir = tmp_path / "data"
    data_dir.mkdir(exist_ok=True)
    start = 0
    for i, size in enumerate(sizes):
        arr = (np.arange(start, start + size) % 65536).astype(np.uint16)
        np.save(data_dir / f"{dataset}_{i:06d}", arr)
        start += size
    return dataset


def test_native_stream_matches_numpy_fallback(workdir, monkeypatch):
    """Every batch from the native mmap stream == the numpy shard-walk,
    across shard boundaries and end-of-stream wraparound."""
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if _native_loader_module() is None:
        import pytest
        pytest.skip("native loader unavailable")
    dataset = _make_shards(workdir, [100, 70, 30])
    monkeypatch.setenv("PENROZ_NATIVE_LOADER", "0")
    fallback = Loader(dataset, buffer_size=64)
    expected = [fallback.next_batch() for _ in range(8)]
    monkeypatch.delenv("PENROZ_NATIVE_LOADER")
    native = Loader(dataset, buffer_size=64)
    for xf, yf in expected:  # 8 × 64 > 200 tokens → wraps the stream
        xn, yn = native.next_batch()
        np.testing.assert_array_equal(xn, xf)
        np.testing.assert_array_equal(yn, yf)
    assert native._stream is not None  # really took the native path
    assert fallback._stream is None


def test_native_stream_rank_strided(workdir, monkeypatch):
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if _native_loader_module() is None:
        import pytest
        pytest.skip("native loader unavailable")
    dataset = _make_shards(workdir, [128, 128])
    # two "ranks" with disjoint strided windows
    for rank in range(2):
        monkeypatch.setenv("PENROZ_NATIVE_LOADER", "0")
        fallback = Loader(dataset, begin_idx=32 * rank, buffer_size=32,
                          idx_offset=64)
        expected = [fallback.next_batch()[0] for _ in range(6)]
        monkeypatch.delenv("PENROZ_NATIVE_LOADER")
        native = Loader(dataset, begin_idx=32 * rank, buffer_size=32,
                        idx_offset=64)
        for xf in expected:
            xn, _ = native.next_batch()
            np.testing.assert_array_equal(xn, xf)


def test_native_stream_picks_up_new_shards(workdir):
    """A shard appended mid-stream (concurrent Downloader) is seen on the
    next batch — the stream rebuilds when the file list changes."""
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if _native_loader_module() is None:
        import pytest
        pytest.skip("native loader unavailable")
    dataset = _make_shards(workdir, [64])
    loader = Loader(dataset, buffer_size=32)
    loader.next_batch()
    total_before = loader._stream.total_tokens if loader._stream else 0
    _make_shards(workdir, [64, 64], dataset=dataset)  # rewrites 0, adds 1
    loader.next_batch()
    assert loader._stream.total_tokens == 128
    assert total_before == 64


def test_native_state_survives_shard_append_after_wrap(workdir, monkeypatch):
    """Regression: after the stream wraps, appending a shard must yield the
    same next batch on native and fallback paths (normalized state)."""
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if _native_loader_module() is None:
        import pytest
        pytest.skip("native loader unavailable")

    def run(native: bool):
        if native:
            monkeypatch.delenv("PENROZ_NATIVE_LOADER", raising=False)
        else:
            monkeypatch.setenv("PENROZ_NATIVE_LOADER", "0")
        for f in (workdir / "data").glob("wrp_*.npy"):
            f.unlink()
        _make_shards(workdir, [100], dataset="wrp")
        loader = Loader("wrp", buffer_size=64)
        for _ in range(5):  # wraps several times
            loader.next_batch()
        _make_shards(workdir, [100, 50], dataset="wrp")  # append a shard
        return loader.next_batch()[0]

    np.testing.assert_array_equal(run(native=True), run(native=False))


def test_native_stream_not_stale_after_delete(workdir):
    """Regression: delete + re-download with identical filenames must not
    serve the deleted files' mmapped pages."""
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if _native_loader_module() is None:
        import pytest
        pytest.skip("native loader unavailable")
    _make_shards(workdir, [64], dataset="del")
    loader = Loader("del", buffer_size=32)
    first, _ = loader.next_batch()
    loader.delete()
    import numpy as _np
    data_dir = workdir / "data"
    _np.save(data_dir / "del_000000",
             _np.full(64, 7, _np.uint16))  # same name, new content
    loader.shard = loader.idx = 0
    fresh, _ = loader.next_batch()
    assert (np.asarray(fresh) == 7).all()
    assert not np.array_equal(first, fresh)


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_next_batch_says_where_its_time_went(workdir, monkeypatch, native):
    """Two running totals on the loader: learning what there is to read
    (glob, stat, a shard's np.load) and bringing the tokens; both grow on
    either path, and together they stay inside the wall time of the calls
    (``penroz/load_batch`` carries their differences as scan_ms /
    gather_ms)."""
    import time
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if native and _native_loader_module() is None:
        pytest.skip("native loader unavailable")
    if not native:
        monkeypatch.setenv("PENROZ_NATIVE_LOADER", "0")
    dataset = _make_shards(workdir, [100, 70, 30])
    loader = Loader(dataset, buffer_size=64)
    assert loader.scan_seconds == 0.0 and loader.gather_seconds == 0.0
    seen = []
    t0 = time.perf_counter()
    for _ in range(8):          # wraps the stream: every shard is loaded
        loader.next_batch()
        seen.append((loader.scan_seconds, loader.gather_seconds))
    wall = time.perf_counter() - t0
    assert (loader._stream is not None) is native
    assert all(b[0] > a[0] and b[1] > a[1]
               for a, b in zip([(0.0, 0.0)] + seen, seen))
    assert loader.scan_seconds + loader.gather_seconds <= wall


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_a_look_the_file_system_does_not_answer_holds_one_batch(
        workdir, monkeypatch, native):
    """A look at the directory that hangs (a checkpoint's flush beside it)
    costs the batch that made it ``SCAN_WAIT_SECONDS`` and later batches
    nothing: they go on over the shards last seen, in order, and the batch
    after the look ends reads what it found."""
    import threading
    import time
    from penroz_tpu.data.loaders import Loader, _native_loader_module
    if native and _native_loader_module() is None:
        pytest.skip("native loader unavailable")
    if not native:
        monkeypatch.setenv("PENROZ_NATIVE_LOADER", "0")
    monkeypatch.setattr(loaders, "SCAN_WAIT_SECONDS", 0.05)
    dataset = _make_shards(workdir, [64])
    loader = Loader(dataset, buffer_size=16)
    first, _ = loader.next_batch()
    answered, files = threading.Event(), loader._files

    def hung():
        assert answered.wait(30)
        return files()

    monkeypatch.setattr(loader, "_files", hung)
    _make_shards(workdir, [64, 64], dataset=dataset)    # adds shard 1
    t0 = time.perf_counter()
    held, _ = loader.next_batch()
    t1 = time.perf_counter()
    later = [loader.next_batch()[0] for _ in range(5)]
    t2 = time.perf_counter()
    assert 0.05 <= t1 - t0 < 5
    assert t2 - t1 < 0.2          # five waits would be 0.25
    # the known shard, wrapping: 16..31, then 32..63, 0..15, ...
    np.testing.assert_array_equal(first, np.arange(16))
    np.testing.assert_array_equal(held, np.arange(16, 32))
    np.testing.assert_array_equal(later[2], np.arange(16))
    answered.set()
    loader._looking.result(30)
    monkeypatch.setattr(loader, "_files", files)
    loader.shard, loader.idx = 0, 56
    crossing, _ = loader.next_batch()       # runs on into the new shard
    np.testing.assert_array_equal(crossing, np.arange(56, 72))
    assert loader._looking is None
