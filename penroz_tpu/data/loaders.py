"""Dataset download/sharding and rank-strided shard loading.

The TPU-native equivalent of the reference's ``loaders.py``:

- ``Downloader`` — HF ``datasets`` → tokenize → fixed-size uint16 ``.npy``
  shards named ``{dataset_id}_{idx:06d}`` (reference: loaders.py:16-41).
  Tokenization fans out over a thread pool (tiktoken/HF tokenizers release
  the GIL in native code; the reference forks a process pool instead,
  loaders.py:29-32, which would fight the JAX runtime here).
- ``Loader`` — stateful ``next_batch`` over the sorted shard sequence with
  shard wraparound/concatenation and rank-strided indexing via
  ``begin_idx``/``idx_offset`` (reference: loaders.py:45-87); targets are the
  input shifted by ``target_offset`` (0 → no targets, for separate target
  datasets in /evaluate/).
"""

from __future__ import annotations

import glob
import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as ScanTimeout

import numpy as np

from penroz_tpu.data.tokenizers import Tokenizer

log = logging.getLogger(__name__)

DATA_FOLDER = "data"
NATIVE_LOADER_ENV = "PENROZ_NATIVE_LOADER"

# ``Loader.next_batch`` looks at its directory on every batch (a shard a
# concurrent Downloader adds is read on the next one), on a helper thread:
# a look that the file system answers in time is the batch's own, as if made
# inline; one that it does not (a checkpoint's flush renaming 6 GB held
# ``glob`` and ``stat`` beside it for 0.1 to 3.4 s, inside a training step)
# costs the batch this much, and is read by the first batch after it ends.
SCAN_WAIT_SECONDS = 0.1


def _native_loader_module():
    if os.environ.get(NATIVE_LOADER_ENV, "1") == "0":
        return None
    from penroz_tpu.utils import native_build
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "_native")
    return native_build.load_extension("penroz_loader", out_dir)


def _npy_payload(path: str):
    """(byte offset, token count) of a uint16 1-D .npy payload, or None."""
    m = np.load(path, mmap_mode="r")
    if m.dtype != np.uint16 or m.ndim != 1:
        return None
    return int(m.offset), int(m.shape[0])


class Loader:
    def __init__(self, dataset_id: str, begin_shard: int = 0,
                 begin_idx: int = 0, buffer_size: int = 1024,
                 idx_offset: int | None = None):
        self.dataset_id = dataset_id
        self.shard = begin_shard
        self.idx = begin_idx
        self.buffer_size = int(buffer_size)
        self.idx_offset = int(idx_offset if idx_offset is not None
                              else buffer_size)
        self._cache: dict[int, np.ndarray] = {}
        self._stream = None          # native mmap stream (penroz_loader)
        self._stream_sig: list[tuple] = []   # (name, size, mtime_ns) per shard
        self._prefix: list[int] = []
        self._seen = None            # newest finished look: (files, sig)
        self._looking = None         # a look the file system has yet to answer
        self._looker = None          # the helper thread, made at first need
        # Where ``next_batch``'s time went, running totals in seconds
        # (``time.perf_counter``): learning what there is to read (the glob,
        # the ``stat``s, a shard's ``np.load``) and bringing the tokens
        # (gather, copy, prefetch; slicing and ``astype``).  A caller reads
        # the differences (``penroz/load_batch``: scan_ms, gather_ms).
        self.scan_seconds = 0.0
        self.gather_seconds = 0.0

    def _files(self) -> list[str]:
        pattern = os.path.join(DATA_FOLDER, f"{self.dataset_id}_*.npy")
        return sorted(os.path.basename(p) for p in glob.glob(pattern))

    def list(self) -> list[str]:
        return self._files()

    def delete(self):
        for name in self._files():
            os.remove(os.path.join(DATA_FOLDER, name))
        self._cache.clear()
        # Drop the mmap stream too: a re-download reusing the same shard
        # filenames must not serve the deleted files' pages.
        self._stream, self._stream_sig, self._prefix = None, [], []
        self._seen = self._looking = None

    def _scan(self) -> tuple[list[str], list[tuple] | None]:
        """The shards' names and each one's (name, size, mtime_ns); no
        second where a shard vanished between the glob and its stat."""
        files = self._files()
        try:
            return files, [(name, st.st_size, st.st_mtime_ns) for name, st in
                           ((n, os.stat(os.path.join(DATA_FOLDER, n)))
                            for n in files)]
        except OSError:
            return files, None

    def _look(self) -> tuple[list[str], list[tuple] | None]:
        """What there is to read, by a look made now where the file system
        answers within ``SCAN_WAIT_SECONDS`` (always, for the first), else
        by the newest one that finished.  While a look is unanswered no
        batch waits for it or starts another."""
        if self._looking is not None:
            if not self._looking.done():
                return self._seen
            self._seen, self._looking = self._looking.result(), None
        if self._looker is None:
            self._looker = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="penroz-loader-scan")
        look = self._looker.submit(self._scan)
        try:
            self._seen = look.result(
                None if self._seen is None else SCAN_WAIT_SECONDS)
        except ScanTimeout:
            self._looking = look
        return self._seen

    def _shard_data(self, files: list[str], shard_idx: int) -> np.ndarray:
        shard_idx %= len(files)
        data = self._cache.get(shard_idx)
        if data is None:
            # keep at most two shards resident (current + wraparound peek)
            if len(self._cache) > 1:
                self._cache.clear()
            t0 = time.perf_counter()
            data = np.load(os.path.join(DATA_FOLDER, files[shard_idx]))
            self.scan_seconds += time.perf_counter() - t0
            self._cache[shard_idx] = data
        return data

    def _native_stream(self, files: list[str], sig: list[tuple] | None):
        """mmap-backed token stream over ``files``; None → numpy fallback.

        Rebuilt whenever any shard's (name, size, mtime) changes (``sig``,
        from ``_scan``) — new shards from a concurrent Downloader, or
        same-name rewrites after a delete + re-download."""
        if sig is None:
            return None
        if sig == self._stream_sig:
            return self._stream
        self._stream, self._stream_sig = None, sig
        module = _native_loader_module()
        if module is None:
            return None
        shards, prefix, total = [], [], 0
        try:
            for name in files:
                path = os.path.join(DATA_FOLDER, name)
                payload = _npy_payload(path)
                if payload is None:
                    return None  # non-uint16 shard: numpy path handles it
                prefix.append(total)
                total += payload[1]
                shards.append((path, payload[0], payload[1]))
            self._stream = module.Stream(shards)
            self._prefix = prefix
        except Exception as e:  # noqa: BLE001
            log.warning("Native loader failed (%s); using numpy path", e)
            self._stream = None
        return self._stream

    def next_batch(self, target_offset: int = 1):
        """(input, target) flat int32 arrays of ``buffer_size`` tokens;
        target is input shifted by ``target_offset`` (None when 0)."""
        t0 = time.perf_counter()
        files, sig = self._look()
        if not files:
            raise ValueError(f"Dataset {self.dataset_id} has no shards")
        need = self.buffer_size + target_offset
        stream = self._native_stream(files, sig)
        t1 = time.perf_counter()
        self.scan_seconds += t1 - t0
        if stream is not None:
            # (shard, idx) → linear stream position, then fold the state
            # back to normalized (shard, idx) exactly as the fallback's
            # shard-walk would — both paths must hold identical state so a
            # mid-run path switch or shard-list change never shifts the
            # window (ranks on different toolchains read the same data).
            pos = (self._prefix[self.shard % len(files)]
                   + self.idx) % stream.total_tokens
            self.shard = max(i for i, p in enumerate(self._prefix)
                             if p <= pos)
            self.idx = pos - self._prefix[self.shard]
            buf = np.empty(need, np.int32)
            stream.gather_into(buf, pos, need)
            x = buf[:self.buffer_size]
            # y copies: x and y must not alias one buffer (the fallback
            # returns independent arrays; mutation semantics must match).
            y = (buf[target_offset:target_offset + self.buffer_size].copy()
                 if target_offset else None)
            self.idx += self.idx_offset
            stream.prefetch(pos + self.idx_offset, need)
            self.gather_seconds += time.perf_counter() - t1
            return x, y
        loaded = self.scan_seconds      # ``_shard_data`` adds its np.load
        self.shard %= len(files)
        data = self._shard_data(files, self.shard)
        while self.idx >= len(data):
            self.idx -= len(data)
            self.shard = (self.shard + 1) % len(files)
            data = self._shard_data(files, self.shard)
        buf = data[self.idx:self.idx + need]
        peek = self.shard
        while len(buf) < need:
            peek = (peek + 1) % len(files)
            extra = self._shard_data(files, peek)
            buf = np.concatenate([buf, extra[:need - len(buf)]])
        x = buf[:self.buffer_size].astype(np.int32)
        y = (buf[target_offset:target_offset + self.buffer_size]
             .astype(np.int32) if target_offset else None)
        self.idx += self.idx_offset
        self.gather_seconds += (time.perf_counter() - t1
                                - (self.scan_seconds - loaded))
        return x, y


class Downloader:
    def __init__(self, dataset_id: str, shard_size: int = 2 ** 24,
                 encoding: str = "tiktoken/gpt2"):
        self.dataset_id = dataset_id
        self.shard_size = int(shard_size)
        self.tokenizer = Tokenizer(encoding)

    def download(self, path: str, name: str | None = None,
                 split: str = "train"):
        """Download + tokenize + write fixed-size uint16 shards (the final
        partial shard is also flushed).  One attempt — bounded retry with
        backoff lives in the API layer (serve/app.py download task), which
        also surfaces terminal failure to clients."""
        from penroz_tpu.utils import faults
        faults.check("data.download")
        import datasets
        ds = datasets.load_dataset(path, name, split=split)
        os.makedirs(DATA_FOLDER, exist_ok=True)
        buffer = np.empty(self.shard_size, np.uint16)
        fill = 0
        shard_idx = 0

        def flush(upto: int):
            nonlocal shard_idx
            # Atomic publish: write to a temp name and os.replace.  A
            # re-download must never truncate a shard inode that a live
            # Loader has mmapped (penroz_loader) — replace swaps the
            # directory entry and the old inode stays valid until unmapped.
            final = os.path.join(DATA_FOLDER,
                                 f"{self.dataset_id}_{shard_idx:06d}.npy")
            tmp = final + ".tmp"
            with open(tmp, "wb") as f:  # np.save on a file object: no
                np.save(f, buffer[:upto])  # surprise .npy suffix appended
            os.replace(tmp, final)
            shard_idx += 1

        workers = max(1, (os.cpu_count() or 2) // 2)
        with ThreadPoolExecutor(max_workers=workers) as pool:
            for tokens in pool.map(self.tokenizer.tokenize, ds["text"],
                                   chunksize=16):
                arr = np.asarray(tokens, np.uint16)
                pos = 0
                while pos < len(arr):
                    take = min(len(arr) - pos, self.shard_size - fill)
                    buffer[fill:fill + take] = arr[pos:pos + take]
                    fill += take
                    pos += take
                    if fill == self.shard_size:
                        flush(fill)
                        fill = 0
        if fill:
            flush(fill)
