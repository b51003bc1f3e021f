"""Model checkpoint I/O with a /dev/shm write-through cache.

Checkpoint = one file holding the layer DSL, the flat param/buffer dicts
(numpy arrays; bf16 via ml_dtypes), the optax optimizer config + state, and
the progress/stats/status JSON — the same logical contents as the
reference's ``torch.save`` blob (neural_net_model.py:98-174), but in a
**non-executable container** (safetensors-style: JSON header + raw array
bytes, below) instead of a pickle: loading a checkpoint can never run code,
unlike ``torch.load``'s pickle VM (SURVEY §7.1's planned upgrade).

Container layout (``MAGIC`` = ``b"PENROZC1"``)::

    MAGIC | uint64-LE header_len | header JSON (utf-8) | array payload

The header's ``tree`` is the checkpoint's JSON structure with every numpy
leaf replaced by ``{"__array__": i}`` and every dict encoded as
``{"__dict__": [[key, value], ...]}`` (preserving int keys, which JSON
objects cannot); ``arrays[i]`` records dtype/shape/offset/nbytes into the
64-byte-aligned payload.  Decoding is pure JSON + ``np.frombuffer``.

Write path: serialize into the shared-memory dir (fast, observable by every
process on the host) and flush to the durable ``models/`` dir in a detached
background process — both behaviors are API-visible (the reference's /dev/shm
cache + async ``shutil.copyfile`` flush, neural_net_model.py:113-122).
"""

from __future__ import annotations

import json
import logging
import os
import platform
import shutil
import struct
import tempfile
import threading
import uuid
import zlib

import numpy as np

from penroz_tpu.utils import tracing

log = logging.getLogger(__name__)

MODELS_FOLDER = "models"
MAGIC = b"PENROZC1"
_ALIGN = 64


def np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including the ml_dtypes families (``bfloat16``,
    ``float8_*``) whose names plain ``np.dtype`` cannot parse."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        try:
            return np.dtype(getattr(ml_dtypes, name))
        except AttributeError:
            raise TypeError(f"unknown checkpoint dtype {name!r}")


def _encode_tree(x, arrays: list):
    """``x`` with its numpy leaves moved to ``arrays`` and named by their
    place there.  A function of the module, not a closure of
    :func:`_encode_parts`: a nested function that calls itself is a
    reference cycle with whatever else it closes over, and ``arrays`` — a
    save's whole host copy, 6 GB of a 510 M-parameter model with AdamW —
    then outlives the save until the next full garbage collection, three
    saves later in a steady training loop (CHANGES.md PR 42)."""
    if isinstance(x, np.ndarray):
        arrays.append(np.ascontiguousarray(x))
        return {"__array__": len(arrays) - 1}
    if isinstance(x, np.generic):  # numpy scalar → python scalar
        return x.item()
    if isinstance(x, dict):
        return {"__dict__": [[k, _encode_tree(v, arrays)]
                             for k, v in x.items()]}
    if isinstance(x, (list, tuple)):
        return [_encode_tree(v, arrays) for v in x]
    return x  # str/int/float/bool/None — json handles or raises


def _encode_parts(data):
    """Split a JSON-able tree with numpy leaves into (header bytes, arrays,
    meta) — the writer streams arrays to the file so multi-GB checkpoints
    never exist as one in-memory blob."""
    arrays: list[np.ndarray] = []
    tree = _encode_tree(data, arrays)
    meta = []
    offset = 0
    for a in arrays:
        offset = -(-offset // _ALIGN) * _ALIGN
        # Per-stream CRC32: bit rot / torn copies surface as a descriptive
        # error at load instead of a garbage decode into live weights.
        # (tobytes() runs again in _write_stream — CPU for the checksum,
        # but peak memory stays max(array), never sum.)
        meta.append({"dtype": str(a.dtype), "shape": list(a.shape),
                     "offset": offset, "nbytes": a.nbytes,
                     "crc32": zlib.crc32(a.tobytes()) & 0xFFFFFFFF})
        offset += a.nbytes
    header = json.dumps({"tree": tree, "arrays": meta},
                        separators=(",", ":")).encode("utf-8")
    return header, arrays, meta


def _write_parts(f, header, arrays, meta) -> int:
    """Write :func:`_encode_parts`' output to a binary file object as the
    container; returns the bytes written."""
    f.write(MAGIC)
    f.write(struct.pack("<Q", len(header)))
    f.write(header)
    written = 0
    for a, m in zip(arrays, meta):
        f.write(b"\0" * (m["offset"] - written))
        # tobytes(): ml_dtypes (bf16) and 0-d/empty arrays don't all
        # support zero-copy buffer export; one-array copies keep peak
        # memory at max(array) instead of sum(arrays).
        f.write(a.tobytes())
        written = m["offset"] + m["nbytes"]
    return 16 + len(header) + written


def _encode(data) -> bytes:
    """Container bytes in memory (tests / small blobs)."""
    import io
    buf = io.BytesIO()
    _write_parts(buf, *_encode_parts(data))
    return buf.getvalue()


def list_model_ids() -> list[str]:
    """Model ids with a main checkpoint blob (durable or shm copy)."""
    import glob
    import re
    ids = set()
    for base in (MODELS_FOLDER, os.path.join(SHM_PATH, MODELS_FOLDER)):
        for path in glob.glob(os.path.join(base, "model_*.ckpt")):
            m = re.match(r"model_(.+?)\.ckpt$", os.path.basename(path))
            # exclude exactly the shard-file suffix (".shard<idx>"), not
            # any id merely containing ".shard"
            if m and not re.search(r"\.shard\d+$", m.group(1)):
                ids.add(m.group(1))
    return sorted(ids)


def _decode_tree(tree, array_leaf):
    """Shared walker for the container's tree encoding; ``array_leaf(i)``
    resolves ``{"__array__": i}`` nodes (payload arrays for full loads,
    ``None`` for header-only peeks).  It calls itself by its module name,
    as :func:`_encode_tree` does and for its reason: a nested walker would
    hold ``array_leaf``, and with it a load's arrays, in a cycle."""
    if isinstance(tree, dict):
        if "__array__" in tree and len(tree) == 1:
            return array_leaf(tree["__array__"])
        return {k: _decode_tree(v, array_leaf) for k, v in tree["__dict__"]}
    if isinstance(tree, list):
        return [_decode_tree(v, array_leaf) for v in tree]
    return tree


def _source_path(model_id: str) -> str:
    shm_path = shm_model_path(model_id)
    return shm_path if os.path.exists(shm_path) else model_path(model_id)


def _read_header(f):
    """Parse the container header; returns (header dict, payload offset)."""
    prefix = f.read(16)
    if prefix[:8] != MAGIC:
        raise ValueError(
            "not a penroz checkpoint (bad magic); legacy pickle "
            "checkpoints are not loaded — re-create or re-import the model")
    (header_len,) = struct.unpack("<Q", prefix[8:16])
    return json.loads(f.read(header_len).decode("utf-8")), 16 + header_len


def peek_tree(model_id: str) -> dict:
    """Decode a checkpoint's metadata tree WITHOUT reading array payloads —
    array leaves come back as ``None``.  Reads only the JSON header, so
    status/progress checks across many large models stay cheap.

    :raises KeyError: if the model was never created.
    """
    try:
        with open(_source_path(model_id), "rb") as f:
            header, _ = _read_header(f)
    except FileNotFoundError:
        raise KeyError(f"Model {model_id} not created yet.")
    return _decode_tree(header["tree"], lambda i: None)


def patch_meta(model_id: str, updates: dict):
    """Rewrite top-level metadata fields (status, progress, ...) without
    decoding or re-encoding the array payload: a new header is written and
    the payload bytes are streamed through verbatim (array offsets are
    payload-relative, so a changed header length does not disturb them).
    ``updates`` values must be array-free (JSON-able + numpy scalars).

    Both copies (shm + durable) are written synchronously — callers patch
    metadata to record a fact (e.g. an orphaned-training Error) and a
    deferred flush could lose it.

    :raises KeyError: if the model was never created.
    """
    # Narrow scope: only a missing SOURCE means "model not created" — a
    # FileNotFoundError from the write loop below (e.g. concurrent delete
    # of models/) must surface as the write failure it is.
    try:
        f = open(_source_path(model_id), "rb")
    except FileNotFoundError:
        raise KeyError(f"Model {model_id} not created yet.")
    with f:
        header, payload_off = _read_header(f)
        pairs = dict(header["tree"]["__dict__"])
        for key, value in updates.items():
            enc_header, arrays, _ = _encode_parts(value)
            if arrays:
                raise ValueError("patch_meta values must be array-free")
            pairs[key] = json.loads(enc_header)["tree"]
        header["tree"]["__dict__"] = [[k, v] for k, v in pairs.items()]
        new_header = json.dumps(header, separators=(",", ":")
                                ).encode("utf-8")
        for dest in (shm_model_path(model_id), model_path(model_id)):
            os.makedirs(os.path.dirname(dest) or ".", exist_ok=True)
            fd, tmp_path = _mkstemp_for(dest)
            try:
                with os.fdopen(fd, "wb") as out:
                    out.write(MAGIC)
                    out.write(struct.pack("<Q", len(new_header)))
                    out.write(new_header)
                    f.seek(payload_off)
                    shutil.copyfileobj(f, out)
                os.replace(tmp_path, dest)
            except BaseException:
                if os.path.exists(tmp_path):
                    os.remove(tmp_path)
                raise


def _read(path: str):
    """Decode a container file via mmap: raw bytes are paged by the kernel
    while each array is copied out, so peak memory is ~sum(arrays), not
    file-size + sum(arrays) (the writer streams for the same reason)."""
    import mmap
    with open(path, "rb") as f:
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except ValueError:  # zero-length file → same error as bad magic
            raise ValueError(
                "not a penroz checkpoint (bad magic); legacy pickle "
                "checkpoints are not loaded — re-create or re-import the "
                "model")
        try:
            return _decode(mm, source=path)
        finally:
            mm.close()


def _decode(buf: bytes, source: str = "<bytes>"):
    """Decode container bytes back into the tree (inverse of ``_encode``).

    Corruption is detected, not propagated: a payload shorter than the
    header promises (truncation) or an array segment whose CRC32 disagrees
    with the header raises a ValueError naming the file and the stream —
    never a garbage decode into live weights or a bare struct error.
    """
    if buf[:8] != MAGIC:
        raise ValueError(
            "not a penroz checkpoint (bad magic); legacy pickle checkpoints "
            "are not loaded — re-create or re-import the model")
    (header_len,) = struct.unpack("<Q", buf[8:16])
    if len(buf) < 16 + header_len:
        raise ValueError(
            f"checkpoint corrupt (truncated header) in {source}: "
            f"header claims {header_len} bytes, file holds "
            f"{len(buf) - 16}")
    header = json.loads(buf[16:16 + header_len].decode("utf-8"))
    payload = memoryview(buf)[16 + header_len:]
    arrays = []
    error = None
    for i, m in enumerate(header["arrays"]):
        end = m["offset"] + m["nbytes"]
        if end > len(payload):
            error = (
                f"checkpoint corrupt (truncated payload) in {source}: "
                f"array stream {i} (dtype {m['dtype']}, shape "
                f"{tuple(m['shape'])}) needs payload bytes "
                f"[{m['offset']}, {end}) but only {len(payload)} exist")
            break
        raw = payload[m["offset"]:end]
        # "crc32" absent = pre-CRC checkpoint: still loadable, unverified.
        expect = m.get("crc32")
        got = (zlib.crc32(raw) & 0xFFFFFFFF) if expect is not None else None
        if got is None or got == expect:
            arrays.append(np.frombuffer(raw, dtype=np_dtype(m["dtype"]))
                          .reshape(m["shape"]).copy())
        else:
            error = (
                f"checkpoint corrupt (CRC32 mismatch) in {source}: "
                f"array stream {i} (dtype {m['dtype']}, shape "
                f"{tuple(m['shape'])}) expected {expect:#010x}, got "
                f"{got:#010x} — the file was truncated, bit-flipped, "
                "or torn by a non-atomic copy")
        # .copy() above detached the numpy view, so the slice can release
        # now — raising with live exports would wedge the caller's
        # mmap.close() (the traceback keeps frame locals alive).
        raw.release()
        if error:
            break
    if error:
        payload.release()
        raise ValueError(error)
    return _decode_tree(header["tree"], arrays.__getitem__)


def detect_shm_path() -> str:
    """Best shared-memory directory for this OS (fallback: tempdir).

    ``PENROZ_SHM_PATH`` overrides — the training worker subprocess
    (models/train_worker.py) must write through the SAME shm dir as the
    serving parent even when a test has repointed the parent's
    ``SHM_PATH`` attribute at a tmpdir."""
    override = os.environ.get("PENROZ_SHM_PATH")
    if override:
        return override
    system = platform.system()
    if system == "Linux" and os.path.isdir("/dev/shm") and os.access("/dev/shm", os.W_OK):
        return "/dev/shm"
    if system == "Darwin" and os.path.isdir("/Volumes/RAMDisk") and os.access("/Volumes/RAMDisk", os.W_OK):
        return "/Volumes/RAMDisk"
    return tempfile.gettempdir()


SHM_PATH = detect_shm_path()


def model_path(model_id: str) -> str:
    return os.path.join(MODELS_FOLDER, f"model_{model_id}.ckpt")


def shm_model_path(model_id: str) -> str:
    return os.path.join(SHM_PATH, model_path(model_id))


def shard_file_path(model_id: str, process_index: int) -> str:
    """Per-host shard file for cross-host-sharded arrays (TP/SP/EP over a
    multi-host mesh).  The main blob keeps metadata + addressable arrays;
    host ``k`` persists the array pieces only it holds."""
    return os.path.join(MODELS_FOLDER,
                        f"model_{model_id}.shard{process_index}.ckpt")


def _shard_indices(model_id: str) -> list[int]:
    """Process indices with an existing shard file (shm or durable),
    discovered by glob so stale non-contiguous leftovers are found too."""
    import glob
    import re
    pattern = f"model_{re.escape(model_id)}.shard*.ckpt"
    indices = set()
    for base in (os.path.join(SHM_PATH, MODELS_FOLDER), MODELS_FOLDER):
        for path in glob.glob(os.path.join(base, pattern)):
            m = re.search(r"\.shard(\d+)\.ckpt$", path)
            if m:
                indices.add(int(m.group(1)))
    return sorted(indices)


def save_shard(model_id: str, process_index: int, data: dict,
               sync_flush: bool = False, world: int | None = None):
    """Persist one host's array shards with the same shm write-through +
    background flush behavior as the main blob.

    The master (index 0) also prunes shard files at indices >= ``world`` —
    leftovers from an earlier run with more processes would otherwise be
    reassembled on load, overwriting fresh weights with stale pieces."""
    os.makedirs(MODELS_FOLDER, exist_ok=True)
    os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
    rel = shard_file_path(model_id, process_index)
    shm_path = os.path.join(SHM_PATH, rel)
    with tracing.span("penroz/ckpt_shard_write") as sp:
        sp.set(bytes=_atomic_write(shm_path, data))
    if sync_flush:
        _flush(shm_path, rel)
    else:
        _spawn_flush(shm_path, rel)
    if process_index == 0 and world is not None:
        for idx in _shard_indices(model_id):
            if idx >= world:
                _remove_shard_files(model_id, idx)


def _remove_quietly(path: str) -> bool:
    """Remove if present; racing removers (concurrent DELETEs, the flush
    thread) must not turn an already-gone file into an exception."""
    try:
        os.remove(path)
        return True
    except FileNotFoundError:
        return False


def _remove_shard_files(model_id: str, idx: int):
    rel = shard_file_path(model_id, idx)
    for path in (os.path.join(SHM_PATH, rel), rel):
        _remove_quietly(path)


def load_shards(model_id: str) -> list[dict]:
    """Every readable shard file for ``model_id`` (shm first, durable
    fallback), in process-index order.  Returns [] when none exist."""
    shards = []
    for idx in _shard_indices(model_id):
        rel = shard_file_path(model_id, idx)
        shm_path = os.path.join(SHM_PATH, rel)
        path = shm_path if os.path.exists(shm_path) else rel
        shards.append(_read(path))
    return shards


# ---------------------------------------------------------------------------
# LoRA adapter checkpoints (models/lora.py, serve/adapters.py)
# ---------------------------------------------------------------------------
#
# Adapters persist through the SAME container format (CRC32 per array
# stream, shm write-through + background durable flush) under their own
# filename family — ``adapter_<id>.ckpt`` never collides with the
# ``model_*`` glob, so list_model_ids / the orphan sweep stay model-only.

def adapter_path(adapter_id: str) -> str:
    return os.path.join(MODELS_FOLDER, f"adapter_{adapter_id}.ckpt")


def shm_adapter_path(adapter_id: str) -> str:
    return os.path.join(SHM_PATH, adapter_path(adapter_id))


def list_adapter_ids() -> list[str]:
    """Adapter ids with a checkpoint blob (durable or shm copy)."""
    import glob
    import re
    ids = set()
    for base in (MODELS_FOLDER, os.path.join(SHM_PATH, MODELS_FOLDER)):
        for path in glob.glob(os.path.join(base, "adapter_*.ckpt")):
            m = re.match(r"adapter_(.+?)\.ckpt$", os.path.basename(path))
            if m:
                ids.add(m.group(1))
    return sorted(ids)


def save_adapter(adapter_id: str, data: dict, sync_flush: bool = False):
    """Persist one adapter blob (shm write-through + background flush —
    the model-checkpoint write path applied to the adapter family)."""
    os.makedirs(MODELS_FOLDER, exist_ok=True)
    os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
    shm_path = shm_adapter_path(adapter_id)
    _atomic_write(shm_path, data)
    if sync_flush:
        _flush(shm_path, adapter_path(adapter_id))
    else:
        _spawn_flush(shm_path, adapter_path(adapter_id))


def load_adapter(adapter_id: str) -> dict:
    """Read an adapter checkpoint (CRC-verified), repopulating the shm
    cache on a miss.  :raises KeyError: if the adapter was never created
    (API maps this to a descriptive 400/404)."""
    shm_path = shm_adapter_path(adapter_id)
    durable_path = adapter_path(adapter_id)
    try:
        if not os.path.exists(shm_path):
            os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
            shutil.copyfile(durable_path, shm_path)
        return _read(shm_path)
    except FileNotFoundError:
        raise KeyError(f"Adapter {adapter_id} not created yet.")


def peek_adapter_tree(adapter_id: str) -> dict:
    """Header-only adapter metadata (status/config/model_id) — array leaves
    come back None.  :raises KeyError: unknown adapter."""
    path = shm_adapter_path(adapter_id)
    if not os.path.exists(path):
        path = adapter_path(adapter_id)
    try:
        with open(path, "rb") as f:
            header, _ = _read_header(f)
    except FileNotFoundError:
        raise KeyError(f"Adapter {adapter_id} not created yet.")
    return _decode_tree(header["tree"], lambda i: None)


def delete_adapter(adapter_id: str):
    """Remove both adapter copies (shm + durable) independently, like
    :func:`delete` does for models."""
    _remove_quietly(shm_adapter_path(adapter_id))
    _remove_quietly(adapter_path(adapter_id))


# ---------------------------------------------------------------------------
# KV page blobs (disaggregated prefill hand-off, serve/decode_scheduler.py)
# ---------------------------------------------------------------------------
#
# The page transport for prefill→decode hand-off rides the SAME container
# format (CRC32 per array stream) but stays shm-only: a blob is a
# transit artifact that lives for one hand-off, so there is no durable
# flush and no background thread.  The ``pageblob_<id>.ckpt`` family never
# collides with the ``model_*`` or ``adapter_*`` globs.

def page_blob_path(blob_id: str) -> str:
    return os.path.join(SHM_PATH, MODELS_FOLDER, f"pageblob_{blob_id}.ckpt")


def save_page_blob(blob_id: str, data: dict):
    """Stage one hand-off blob in shm (atomic write, CRC per stream).
    Shm-only on purpose — a crash just orphans a transit file that
    :func:`delete_page_blob` or the tmpdir teardown reclaims."""
    os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
    _atomic_write(page_blob_path(blob_id), data)


def load_page_blob(blob_id: str) -> dict:
    """Read a staged hand-off blob (CRC-verified).  :raises KeyError: if
    the blob was never staged or already consumed."""
    try:
        return _read(page_blob_path(blob_id))
    except FileNotFoundError:
        raise KeyError(f"Page blob {blob_id} not staged.")


def delete_page_blob(blob_id: str) -> bool:
    """Reclaim a consumed (or abandoned) hand-off blob."""
    return _remove_quietly(page_blob_path(blob_id))


def page_blob_nbytes(blob: dict) -> int:
    """Payload size of a hand-off blob: the KV page planes (+ int8 scale
    planes) it carries, summed over layers.  Works on host arrays (staged
    blob codec) and device arrays (d2d transport) alike — the
    ``penroz_disagg_handoff_bytes`` histogram observes through here for
    both, so the two transports' size distributions are comparable."""
    total = 0
    for key in ("k", "v", "k_scale", "v_scale"):
        for plane in blob.get(key, ()):
            total += int(plane.nbytes)
    ssm = blob.get("ssm")
    if ssm is not None:
        # Recurrent rows hand off a constant-size state plane per SSM
        # layer alongside (or instead of) the token-extent KV pages.
        for plane in ssm.get("state", ()):
            total += int(plane.nbytes)
    return total


# ---------------------------------------------------------------------------
# Tier blobs (session hibernation disk tier, serve/tierstore.py)
# ---------------------------------------------------------------------------
#
# Same CRC container as the hand-off page blobs, but a DIFFERENT lifetime:
# a tier blob is a hibernated session's KV, expected to outlive engine
# restarts and ``decode_scheduler.reset()``.  The family therefore lives in
# its own directory (``PENROZ_TIER_DISK_PATH``, default a ``tier/`` subdir
# of the shm models dir) so reset-time page-blob sweeps and the
# ``model_*``/``adapter_*``/``pageblob_*`` globs never touch it.

TIER_DISK_ENV = "PENROZ_TIER_DISK_PATH"


def tier_dir() -> str:
    override = os.environ.get(TIER_DISK_ENV)
    if override:
        return override
    return os.path.join(SHM_PATH, MODELS_FOLDER, "tier")


def tier_blob_path(blob_id: str) -> str:
    return os.path.join(tier_dir(), f"tierblob_{blob_id}.ckpt")


def save_tier_blob(blob_id: str, data: dict):
    """Persist one hibernated-session blob (atomic write, CRC per stream)."""
    os.makedirs(tier_dir(), exist_ok=True)
    _atomic_write(tier_blob_path(blob_id), data)


def load_tier_blob(blob_id: str) -> dict:
    """Read a hibernated-session blob.  :raises KeyError: never saved or
    already reclaimed; :raises ValueError: CRC/container corruption (the
    tier store maps this to a miss + ``penroz_tier_corrupt_blobs_total``)."""
    try:
        return _read(tier_blob_path(blob_id))
    except FileNotFoundError:
        raise KeyError(f"Tier blob {blob_id} not saved.")


def delete_tier_blob(blob_id: str) -> bool:
    return _remove_quietly(tier_blob_path(blob_id))


def tier_blob_nbytes(blob_id: str) -> int:
    """On-disk size of a stored tier blob (0 if missing) — the disk-tier
    byte accounting reads the container size, not the decoded payload, so
    quota math matches what ``du`` would say."""
    try:
        return os.path.getsize(tier_blob_path(blob_id))
    except OSError:
        return 0


def list_tier_blob_ids() -> list[str]:
    """Session ids with a tier blob on disk.  Temp siblings from torn
    atomic writes (``*.ckpt.<hex>``) don't match the glob — the restart
    sweep handles those separately."""
    import glob
    import re
    ids = []
    for path in glob.glob(os.path.join(tier_dir(), "tierblob_*.ckpt")):
        m = re.match(r"tierblob_(.+?)\.ckpt$", os.path.basename(path))
        if m:
            ids.append(m.group(1))
    return sorted(ids)


def validate_tier_blob(blob_id: str) -> bool:
    """Cheap container-header check (magic + parseable header JSON) for
    the restart recovery scan — full per-stream CRC verification still
    happens at :func:`load_tier_blob` time."""
    try:
        with open(tier_blob_path(blob_id), "rb") as f:
            _read_header(f)
        return True
    except (OSError, ValueError, KeyError, struct.error):
        return False


def sweep_tier_orphans(referenced_ids) -> dict:
    """Startup sweep of the tier dir: remove (a) temp siblings a crash
    left behind mid-atomic-write (``tierblob_*.ckpt.<12-hex>`` — torn
    bytes that would silently consume disk-cap budget forever) and
    (b) finished blobs no journal-recovered or live session references
    (unreachable orphans).  ``referenced_ids=None`` means the reference
    set is UNKNOWN (journal replay failed) — temps are still safe to
    reap, but no finished blob is touched, so a transient replay error
    never destroys recoverable sessions.  Returns removal counts."""
    import glob
    import re
    referenced = None if referenced_ids is None else set(referenced_ids)
    temps = blobs = 0
    d = tier_dir()
    if not os.path.isdir(d):
        return {"temp_files_swept": 0, "blobs_swept": 0}
    for path in glob.glob(os.path.join(d, "tierblob_*.ckpt.*")):
        if re.search(r"\.ckpt\.[0-9a-f]{12}$", path) and _remove_quietly(path):
            temps += 1
    for path in glob.glob(os.path.join(d, "tierblob_*.ckpt")):
        if referenced is None:
            break
        m = re.match(r"tierblob_(.+?)\.ckpt$", os.path.basename(path))
        if m and m.group(1) not in referenced and _remove_quietly(path):
            blobs += 1
    if temps or blobs:
        log.info("tier sweep: removed %d orphan temp file(s), %d "
                 "unreferenced blob(s) from %s", temps, blobs, d)
    return {"temp_files_swept": temps, "blobs_swept": blobs}


def save(model_id: str, data: dict, sync_flush: bool = False) -> int:
    """Write checkpoint to shm and flush to disk in the background;
    returns the checkpoint's size in bytes.

    Both writes are atomic (temp file + rename) so concurrent readers —
    cross-process ``load()`` on shm, the background flush on durable — never
    observe a half-written checkpoint.
    """
    os.makedirs(MODELS_FOLDER, exist_ok=True)
    os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
    shm_path = shm_model_path(model_id)
    durable_path = model_path(model_id)
    log.info("Caching model to %s...", shm_path)
    nbytes = _atomic_write(shm_path, data)
    log.info("Model cached successfully: %s", shm_path)
    if sync_flush:
        _flush(shm_path, durable_path)
    else:
        # Background flush: a thread, not a fork — os.fork() deadlocks under
        # JAX's thread pool, and the copy is pure file I/O anyway.
        log.info("Offload flushing model cache %s to %s...", shm_path, durable_path)
        _spawn_flush(shm_path, durable_path)
    return nbytes


def _mkstemp_for(path: str):
    """Unique temp sibling of ``path`` with plain-open() permissions.

    ``os.open(..., 0o666)`` lets the kernel apply the process umask at
    creation — the same semantics as the reference's plain ``open(path,
    "wb")`` writes (neural_net_model.py:116): a permissive umask yields
    cross-user-readable shm checkpoints, a hardened one keeps them private.
    Avoids both mkstemp's unconditional 0600 and probing the process-global
    umask (racy under threads).  O_CLOEXEC keeps the fd out of spawned
    subprocesses."""
    directory = os.path.dirname(path) or "."
    base = os.path.basename(path)
    while True:
        tmp_path = os.path.join(directory, f"{base}.{uuid.uuid4().hex[:12]}")
        try:
            fd = os.open(tmp_path,
                         os.O_CREAT | os.O_EXCL | os.O_WRONLY | os.O_CLOEXEC,
                         0o666)
            return fd, tmp_path
        except FileExistsError:
            continue


def _atomic_write(path: str, data: dict) -> int:
    """Encode ``data`` and write it to ``path`` through a temp sibling and a
    rename; returns the file's size in bytes."""
    from penroz_tpu.utils import faults
    faults.check("ckpt.write")
    with tracing.span("penroz/ckpt_encode"):
        parts = _encode_parts(data)
    with tracing.span("penroz/ckpt_write") as sp:
        fd, tmp_path = _mkstemp_for(path)
        try:
            with os.fdopen(fd, "wb") as f:
                nbytes = _write_parts(f, *parts)
            os.replace(tmp_path, path)
        except BaseException:
            if os.path.exists(tmp_path):
                os.remove(tmp_path)
            raise
        sp.set(bytes=nbytes)
    return nbytes


_FLUSH_THREADS: list = []


def _spawn_flush(shm_path: str, durable_path: str):
    """Background flush thread, tracked so callers can drain before
    deleting the source (a delete racing an in-flight flush is harmless
    but logs a 'source vanished' warning)."""
    _FLUSH_THREADS[:] = [t for t in _FLUSH_THREADS if t.is_alive()]
    # A plain thread does not inherit the caller's context: hand it the
    # trace binding, so the flush is a child of the save that spawned it.
    binding = tracing.capture()

    def flush():
        with tracing.use(binding):
            _flush(shm_path, durable_path)

    t = threading.Thread(target=flush, daemon=True)
    _FLUSH_THREADS.append(t)
    t.start()


def join_flushes(timeout: float = 10.0):
    """Wait for in-flight background flushes (per-thread timeout)."""
    for t in list(_FLUSH_THREADS):
        t.join(timeout)
    _FLUSH_THREADS[:] = [t for t in _FLUSH_THREADS if t.is_alive()]


def _flush(shm_path: str, durable_path: str):
    tmp_path = None
    with tracing.span("penroz/ckpt_flush") as sp:
        try:
            # Unique temp name: overlapping flushes of the same model must
            # not interleave writes into one file.
            fd, tmp_path = _mkstemp_for(durable_path)
            os.close(fd)
            shutil.copyfile(shm_path, tmp_path)
            sp.set(bytes=os.path.getsize(tmp_path))
            os.replace(tmp_path, durable_path)
            if not os.path.exists(shm_path):
                # delete() ran mid-flush: don't resurrect the durable copy
                os.remove(durable_path)
                log.warning("Flush rolled back, model deleted: %s",
                            durable_path)
        except FileNotFoundError:
            # Model deleted (or workdir cleaned) between save and flush.
            log.warning("Flush skipped, source vanished: %s", shm_path)
        finally:
            if tmp_path is not None and os.path.exists(tmp_path):
                os.remove(tmp_path)


def load(model_id: str) -> dict:
    """Read checkpoint, repopulating the shm cache on a miss.

    :raises KeyError: if the model was never created (API maps this to 404).
    """
    shm_path = shm_model_path(model_id)
    durable_path = model_path(model_id)
    try:
        if not os.path.exists(shm_path):
            log.info("Cache miss: copying from %s", durable_path)
            os.makedirs(os.path.join(SHM_PATH, MODELS_FOLDER), exist_ok=True)
            shutil.copyfile(durable_path, shm_path)
        return _read(shm_path)
    except FileNotFoundError as e:
        log.error("File not found error occurred: %s", e)
        raise KeyError(f"Model {model_id} not created yet.")


def delete(model_id: str):
    """Remove the shm cache copy, the durable checkpoint, and shard files.

    The reference removes both copies (neural_net_model.py:239-248) but its
    missing-shm short-circuit would leave the durable file behind after e.g.
    a reboot cleared /dev/shm; here each copy is removed independently so a
    deleted model can never be resurrected by a cache-miss reload.
    """
    removed = _remove_quietly(shm_model_path(model_id))
    if not removed:
        log.warning("Failed to delete (no shm copy): %s",
                    shm_model_path(model_id))
    # Durable copy removed independently — a cleared /dev/shm (e.g. reboot)
    # must not leave a resurrectable durable checkpoint behind.
    _remove_quietly(model_path(model_id))
    for idx in _shard_indices(model_id):
        _remove_shard_files(model_id, idx)
