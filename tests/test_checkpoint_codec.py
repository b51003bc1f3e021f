"""Checkpoint container codec: non-executable load (no pickle).

The reference persists models as ``torch.save`` pickles
(neural_net_model.py:116) whose load can execute arbitrary code; the
penroz container is JSON header + raw array bytes (checkpoint.py module
docstring), so these tests pin round-trip fidelity — including the bits
pickle got for free: int dict keys, bf16 dtypes, nested structure — and
that pickle bytes are rejected outright.
"""

import pickle

import ml_dtypes
import numpy as np
import pytest

from penroz_tpu.utils import checkpoint


def _roundtrip(data):
    return checkpoint._decode(checkpoint._encode(data))


def test_roundtrip_nested_tree_with_arrays():
    data = {
        "layers": [{"linear": {"in_features": 4, "out_features": 2}}],
        "params": {
            "layers.0.weight": np.arange(8, dtype=np.float32).reshape(4, 2),
            "layers.0.bias": np.zeros(2, dtype=ml_dtypes.bfloat16),
        },
        "opt_state_leaves": {0: np.int32(3), 1: np.ones(2, np.float64)},
        "status": {"code": "Trained", "message": None},
        "avg_cost": 1.5,
        "progress": [{"epoch": 0, "cost": 2.0, "ok": True}],
        "unicode": "penröz ✓",
    }
    out = _roundtrip(data)
    assert out["layers"] == data["layers"]
    np.testing.assert_array_equal(out["params"]["layers.0.weight"],
                                  data["params"]["layers.0.weight"])
    assert out["params"]["layers.0.bias"].dtype == ml_dtypes.bfloat16
    # int dict keys survive (JSON objects alone cannot express them)
    assert set(out["opt_state_leaves"]) == {0, 1}
    # numpy scalars come back as python scalars
    assert out["opt_state_leaves"][0] == 3
    assert out["status"] == data["status"]
    assert out["progress"] == data["progress"]
    assert out["unicode"] == data["unicode"]


def test_roundtrip_noncontiguous_and_empty_arrays():
    base = np.arange(24, dtype=np.float32).reshape(4, 6)
    data = {"t": base[:, ::2], "empty": np.zeros((0, 3), np.int8)}
    out = _roundtrip(data)
    np.testing.assert_array_equal(out["t"], base[:, ::2])
    assert out["empty"].shape == (0, 3)
    assert out["empty"].dtype == np.int8


def test_host_arrays_die_with_the_save_or_load_that_made_them():
    """What a save copied to the host is freed when the save returns, by
    reference counting alone: no reference cycle of the encoder holds the
    arrays for the next full garbage collection to find (a nested ``enc``
    that called itself did — 6 GB a save in the looped cell, three saves
    deep before a collection, which a 40 GiB host does not hold: PR 42)."""
    import gc
    import weakref
    a = np.arange(12.0).reshape(3, 4)       # contiguous: kept as it is
    b = np.arange(6, dtype=np.int32)[::2]   # not: the encoder's own copy
    alive = weakref.ref(a)
    gc.collect()
    gc.disable()
    try:
        header, arrays, meta = checkpoint._encode_parts(
            {"params": {"w": a}, "opt": [1, (2.0, b)], "again": a})
        assert arrays[0] is a and len(arrays) == len(meta) == 3
        copied = weakref.ref(arrays[1])
        del arrays, a
        assert alive() is None and copied() is None
        # … and what a load read dies with the loaded tree
        loaded = checkpoint._decode(checkpoint._encode({"w": np.ones(3)}))
        read = weakref.ref(loaded["w"])
        del loaded
        assert read() is None
    finally:
        gc.enable()


def test_shard_pieces_shape_survives():
    """The shard-file payload shape: pieces are (ranges, array) pairs whose
    tuples become lists — reassembly unpacks them positionally."""
    data = {"tag": 7, "pieces": {"w": [(((0, 2), (0, 4)),
                                        np.ones((2, 4), np.float32))]}}
    out = _roundtrip(data)
    (ranges, arr), = out["pieces"]["w"]
    assert [tuple(r) for r in ranges] == [(0, 2), (0, 4)]
    np.testing.assert_array_equal(arr, np.ones((2, 4), np.float32))


def test_pickle_bytes_rejected():
    blob = pickle.dumps({"params": {}}, protocol=5)
    with pytest.raises(ValueError, match="bad magic"):
        checkpoint._decode(blob)


def test_payload_alignment():
    buf = checkpoint._encode({"a": np.ones(3, np.float32),
                              "b": np.ones(5, np.int8),
                              "c": np.ones(2, np.float32)})
    import json as _json
    import struct as _struct
    (hlen,) = _struct.unpack("<Q", buf[8:16])
    header = _json.loads(buf[16:16 + hlen])
    for m in header["arrays"]:
        assert m["offset"] % 64 == 0


def test_np_dtype_resolves_ml_dtypes_and_rejects_unknown():
    assert checkpoint.np_dtype("bfloat16") == np.dtype(ml_dtypes.bfloat16)
    assert checkpoint.np_dtype("float32") == np.dtype(np.float32)
    with pytest.raises(TypeError, match="unknown checkpoint dtype"):
        checkpoint.np_dtype("not_a_dtype")


def test_patch_meta_header_only_rewrite(tmp_path, monkeypatch):
    """patch_meta must update metadata fields and stream the array payload
    through byte-identically, without ever decoding it."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(checkpoint, "SHM_PATH", str(tmp_path / "shm"))
    arr = np.arange(64, dtype=np.float32).reshape(8, 8)
    checkpoint.save("pm", {"status": {"code": "Training", "message": None},
                           "params": {"w": arr}, "progress": [1, 2]},
                    sync_flush=True)
    checkpoint.patch_meta("pm", {"status": {"code": "Error",
                                            "message": "interrupted"}})
    out = checkpoint.load("pm")
    assert out["status"] == {"code": "Error", "message": "interrupted"}
    assert out["progress"] == [1, 2]
    np.testing.assert_array_equal(out["params"]["w"], arr)
    # peek agrees and never touches arrays
    peek = checkpoint.peek_tree("pm")
    assert peek["status"]["code"] == "Error"
    assert peek["params"]["w"] is None
    # array-carrying updates are rejected
    with pytest.raises(ValueError, match="array-free"):
        checkpoint.patch_meta("pm", {"params": {"w": arr}})
    with pytest.raises(KeyError):
        checkpoint.patch_meta("nope", {"status": {}})


def _save_corruptible(tmp_path, monkeypatch, model_id="crc"):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(checkpoint, "SHM_PATH", str(tmp_path / "shm"))
    arr = np.arange(256, dtype=np.float32).reshape(16, 16)
    checkpoint.save(model_id, {"status": {"code": "Trained"},
                               "params": {"w": arr}}, sync_flush=True)
    return checkpoint.shm_model_path(model_id), arr


def test_corrupt_checkpoint_bit_flip_named_in_error(tmp_path, monkeypatch):
    """A single flipped payload byte must fail the per-stream CRC32 with
    the file path and the offending stream named — never a silent garbage
    decode into live weights."""
    path, arr = _save_corruptible(tmp_path, monkeypatch)
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0x40  # one bit, deep in the array payload
    with open(path, "wb") as f:
        f.write(blob)
    with pytest.raises(ValueError) as exc:
        checkpoint.load("crc")
    msg = str(exc.value)
    assert "CRC32 mismatch" in msg
    assert path in msg                 # which file
    assert "array stream 0" in msg     # which stream
    assert "float32" in msg


def test_truncated_checkpoint_named_in_error(tmp_path, monkeypatch):
    """A truncated container (killed copy, full disk) raises a descriptive
    truncation error instead of a bare struct/frombuffer error."""
    path, arr = _save_corruptible(tmp_path, monkeypatch, "trunc")
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) - arr.nbytes // 2])
    with pytest.raises(ValueError) as exc:
        checkpoint.load("trunc")
    msg = str(exc.value)
    assert "truncated" in msg
    assert path in msg
    assert "array stream 0" in msg


def test_pre_crc_checkpoints_still_load(tmp_path, monkeypatch):
    """Checkpoints written before the CRC field existed (no "crc32" in the
    array meta) must keep loading — verification is opportunistic."""
    import json as _json
    import struct as _struct
    buf = checkpoint._encode({"w": np.arange(8, dtype=np.int32)})
    (hlen,) = _struct.unpack("<Q", buf[8:16])
    header = _json.loads(buf[16:16 + hlen])
    for m in header["arrays"]:
        del m["crc32"]
    new_header = _json.dumps(header, separators=(",", ":")).encode()
    legacy = (buf[:8] + _struct.pack("<Q", len(new_header)) + new_header
              + buf[16 + hlen:])
    out = checkpoint._decode(legacy)
    np.testing.assert_array_equal(out["w"], np.arange(8, dtype=np.int32))


def test_list_model_ids_shard_suffix_only(tmp_path, monkeypatch):
    """Only the exact '.shard<idx>' suffix marks a shard file; a model id
    that merely contains '.shard' must stay visible."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(checkpoint, "SHM_PATH", str(tmp_path / "shm"))
    for mid in ("plain", "v1.sharded", "odd.shard"):
        checkpoint.save(mid, {"status": {"code": "Created"}},
                        sync_flush=True)
    checkpoint.save_shard("plain", 1, {"tag": 0, "pieces": {}},
                          sync_flush=True)
    assert checkpoint.list_model_ids() == ["odd.shard", "plain", "v1.sharded"]


def test_page_blob_save_load_delete(tmp_path, monkeypatch):
    """Disaggregated-prefill transport: a staged page blob round-trips
    arrays and scalar leaves through the CRC-checked container, load of a
    missing id is a typed KeyError, and delete is idempotent."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(checkpoint, "SHM_PATH", str(tmp_path / "shm"))
    blob = {"page_size": 4, "pages": 2, "length": 7, "quantized": False,
            "first_token": 42,
            "k": [np.arange(32, dtype=np.float32).reshape(2, 16)],
            "v": [np.arange(32, 64, dtype=np.float32).reshape(2, 16)]}
    checkpoint.save_page_blob("h1", blob)
    out = checkpoint.load_page_blob("h1")
    assert out["page_size"] == 4 and out["length"] == 7
    assert out["first_token"] == 42 and out["quantized"] is False
    np.testing.assert_array_equal(out["k"][0], blob["k"][0])
    np.testing.assert_array_equal(out["v"][0], blob["v"][0])
    assert checkpoint.delete_page_blob("h1") is True
    assert checkpoint.delete_page_blob("h1") is False   # idempotent
    with pytest.raises(KeyError, match="h1"):
        checkpoint.load_page_blob("h1")
