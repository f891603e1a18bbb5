"""DataLoader: prefetched, shuffled batches from in-memory or on-disk rows.

Facade over two engines with identical semantics:

- **native** (default when a C++ toolchain exists): the multi-threaded
  row-gather pipeline in ``native/dataloader.cc`` — batches are assembled by
  C++ threads without the GIL while the accelerator runs the previous step,
  the role TF's C++ input-pipeline/queue kernels played for the reference.
- **python**: plain numpy gathering, same batch order bit-for-bit (the
  shuffle is splitmix64-based in both), used as fallback and as the test
  oracle for the native engine.

Each feature may be a single array or a list of row-shard arrays; sharded
``np.memmap`` features (``DataLoader.from_files`` / ``files.load_dataset``)
stream larger-than-RAM datasets straight from the page cache — the native
engine gathers rows from the mapped shards with no Python on the hot path
(the reference's C++ TFRecord input pipelines,
``examples/benchmark/utils/input_pipeline.py``, played this role).

Batch order is deterministic given (seed, batch_size, drop_remainder)
regardless of engine, thread count, or shard layout.

Optionally binds a :class:`~autodist_tpu.kernel.lowering.ShardingPlan` so
every yielded batch is already ``device_put`` along the mesh data axis (the
remapper's feed-splitting contract, reference remapper.py:81-123).
"""
from __future__ import annotations

import ctypes
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from autodist_tpu.data import _build
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.utils import logging


def _splitmix64(x: int) -> tuple:
    x = (x + 0x9E3779B97F4A7C15) & (2**64 - 1)
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & (2**64 - 1)
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & (2**64 - 1)
    return x, z ^ (z >> 31)


def _epoch_perm(n_rows: int, epoch: int, seed: int, shuffle: bool) -> np.ndarray:
    """The exact permutation the native engine uses (dataloader.cc EpochPerm)."""
    perm = np.arange(n_rows, dtype=np.uint64)
    if not shuffle:
        return perm
    s = (seed ^ ((0x5851F42D4C957F2D * (epoch + 1)) & (2**64 - 1))) & (2**64 - 1)
    for i in range(n_rows - 1, 0, -1):
        s, r = _splitmix64(s)
        j = r % (i + 1)
        perm[i], perm[j] = perm[j], perm[i]
    return perm


class DataLoader:
    """Iterate dict-of-arrays data as prefetched batches.

    ``data``: mapping name -> np.ndarray (or list of row-shard arrays, e.g.
    the mmap'd shards from ``files.load_dataset``), all with equal total
    rows. ``epochs``: -1 repeats forever. ``plan``: optional ShardingPlan;
    when given, batches come back as jax Arrays sharded along the data axis.
    ``transform``: optional host-side ``f(batch, step) -> batch`` hook
    applied to every gathered batch before device transfer — the
    decode/augment stage (see ``data/imagenet.py``); must be deterministic
    in ``(batch, step)`` for multi-host consistency.
    """

    def __init__(
        self,
        data: Dict[str, Any],
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        epochs: int = 1,
        capacity: int = 4,
        num_threads: int = 2,
        engine: str = "auto",      # auto | native | python
        plan: Any = None,
        device_prefetch: int = 0,
        transform: Optional[Callable[[Dict[str, np.ndarray], int], Dict[str, np.ndarray]]] = None,
    ):
        if not data:
            raise ValueError("data must have at least one feature array")
        self.names = sorted(data)
        # Normalize every feature to a list of row shards. ascontiguousarray
        # is a no-op view for already-contiguous inputs — crucially including
        # np.memmap shards, which must NOT be copied into RAM here.
        self.sources: List[List[np.ndarray]] = []
        for k in self.names:
            v = data[k]
            # A list/tuple is a shard list ONLY when every element is
            # already an ndarray — a nested python list like [[0, 1], [2, 3]]
            # is one array-like (and must not be silently re-read as two
            # scalar-row shards).
            if (isinstance(v, (list, tuple)) and v
                    and all(isinstance(s, np.ndarray) for s in v)):
                shards = list(v)
            else:
                shards = [np.asarray(v)]
            if not all(s.ndim >= 1 for s in shards):
                raise ValueError(f"feature {k!r} shards must have a row dim")
            # Preserve already-contiguous arrays as-is (ascontiguousarray
            # would rewrap np.memmap shards as plain ndarray views; same
            # mapped data, but keeping the memmap type makes "not copied"
            # checkable).
            shards = [
                s if (isinstance(s, np.ndarray) and s.flags.c_contiguous)
                else np.ascontiguousarray(s)
                for s in shards
            ]
            tails = {(s.dtype, s.shape[1:]) for s in shards}
            if len(tails) != 1:
                raise ValueError(
                    f"feature {k!r} shards disagree on dtype/row shape: {tails}")
            self.sources.append(shards)
        self.transform = transform
        n_rows = {sum(s.shape[0] for s in shards) for shards in self.sources}
        if len(n_rows) != 1:
            raise ValueError(
                f"feature arrays disagree on total rows (leading dims): {n_rows}")
        self.n_rows = n_rows.pop()
        # Per-feature prefix-sum shard offsets (python-engine gather + native
        # shard tables share this).
        self._offsets = [
            np.cumsum([0] + [s.shape[0] for s in shards])[:-1]
            for shards in self.sources
        ]
        if batch_size <= 0 or batch_size > self.n_rows:
            raise ValueError(
                f"batch_size {batch_size} invalid for {self.n_rows} rows"
            )
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.epochs = epochs
        self.capacity = capacity
        self.num_threads = num_threads
        self.plan = plan
        self.device_prefetch = device_prefetch

        if engine not in ("auto", "native", "python"):
            raise ValueError(
                f"unknown engine {engine!r}; choose auto, native or python"
            )
        lib = _build.load_library() if engine in ("auto", "native") else None
        if engine == "native" and lib is None:
            raise RuntimeError("native engine requested but unavailable")
        self.engine = "native" if lib is not None else "python"
        self._lib = lib

    @property
    def batches_per_epoch(self) -> int:
        full = self.n_rows // self.batch_size
        if self.drop_remainder or self.n_rows % self.batch_size == 0:
            return full
        return full + 1

    def __len__(self) -> int:
        if self.epochs < 0:
            raise TypeError("infinite loader has no len()")
        return self.epochs * self.batches_per_epoch

    # ------------------------------------------------------------------- iter
    def _check_multihost_remainder(self) -> None:
        import jax

        if (jax.process_count() > 1 and not self.drop_remainder
                and self.n_rows % self.batch_size):
            raise ValueError(
                "multi-host DataLoader requires drop_remainder=True: a "
                "ragged final batch cannot assemble into a global array")

    def _with_transform(self, it) -> Iterator[Dict[str, np.ndarray]]:
        if self.transform is None:
            yield from it
            return
        for step, batch in enumerate(it):
            yield self.transform(batch, step)

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        it = self._with_transform(
            self._iter_native() if self.engine == "native" else self._iter_python()
        )
        if self.plan is None:
            return it
        self._check_multihost_remainder()
        if self.device_prefetch > 0:
            return self._iter_device_prefetch(it, self.device_prefetch)
        return (self._shard(b) for b in it)

    def host_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        """Raw per-process host batches, no device transfer.

        The windowed-fit bridge (``DistributedTrainStep.fit(window=k)``)
        stacks ``k`` of these and ships ONE transfer per window
        (``ShardingPlan.window_from_local``) — stacking must happen before
        the device put, so this bypasses the per-batch ``_shard`` path.
        The multi-host ragged-tail contract is the same as ``__iter__``'s:
        a final batch that can't assemble into a global array fails here,
        loudly, not deep inside window assembly. The production of each
        batch (the wait on the native ring, the copy out, the transform)
        is one ``input.next`` span.
        """
        self._check_multihost_remainder()
        return self._spanned(self._with_transform(
            self._iter_native() if self.engine == "native" else self._iter_python()
        ))

    @staticmethod
    def _spanned(it) -> Iterator[Dict[str, np.ndarray]]:
        it = iter(it)
        while True:
            with obs_spans.span("input.next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch

    @classmethod
    def from_files(cls, data_dir: str, batch_size: int,
                   process_slice: bool = False, **kwargs) -> "DataLoader":
        """Open a ``files.write_dataset`` directory as a streaming loader.

        Every shard arrives as an ``np.memmap`` view; rows are gathered
        (by the native engine when available) straight from the page cache,
        so the dataset may be far larger than RAM.

        ``process_slice=True`` is the multi-host recipe: every process
        opens the same (shared-filesystem) directory but keeps only its
        contiguous ``n_rows / process_count`` row range, so the loader's
        local batches assemble into disjoint global batches via ``plan``
        exactly like per-host in-memory data. Requires the process count
        to divide the row count evenly.
        """
        from autodist_tpu.data.files import load_dataset, slice_rows

        data = load_dataset(data_dir)
        if process_slice:
            import jax

            P, p = jax.process_count(), jax.process_index()
            n = sum(s.shape[0] for s in next(iter(data.values())))
            if n % P:
                raise ValueError(
                    f"process_slice needs rows % processes == 0; "
                    f"{n} rows over {P} processes")
            rpp = n // P
            data = slice_rows(data, p * rpp, (p + 1) * rpp)
        return cls(data, batch_size, **kwargs)

    def _iter_device_prefetch(self, it, depth: int):
        """Keep ``depth`` sharded batches in flight ahead of the consumer.

        ``device_put`` dispatches asynchronously, so issuing the next
        window's transfer before the consumer needs it overlaps host→device
        copies with device compute (the flax ``prefetch_to_device`` pattern).
        OPT-IN (``device_prefetch=N``): each batch in flight holds its HBM
        until consumed, which a model sized to fill the chip cannot spare."""
        from collections import deque

        q = deque()
        for b in it:
            q.append(self._shard(b))
            if len(q) > depth:  # keep `depth` transfers in flight past the yielded one
                yield q.popleft()
        while q:
            yield q.popleft()

    def _shard(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """This process's batch is its local slice of the global batch —
        the plan dispatches: single-process device_put vs multi-host
        assembly (each host loads 1/P of the data, the reference's
        per-worker feed-splitting contract in reverse).

        Every loader leaf is batched by construction (row-sliced from the
        dataset), so the broadcast mask is explicitly all-False: a per-host
        batch of 1 must concatenate across hosts, not be misread as a
        replicated broadcast leaf by the dim-1 convention."""
        return self.plan.global_batch_from_local(
            batch, broadcast={name: False for name in batch})

    def _gather(self, i: int, idx: np.ndarray) -> np.ndarray:
        """Gather global rows ``idx`` of feature ``i`` across its shards."""
        shards = self.sources[i]
        if len(shards) == 1:
            return shards[0][idx]
        offsets = self._offsets[i]
        which = np.searchsorted(offsets, idx, side="right") - 1
        out = np.empty((len(idx),) + shards[0].shape[1:], shards[0].dtype)
        for s in np.unique(which):
            m = which == s
            out[m] = shards[s][idx[m] - offsets[s]]
        return out

    def _iter_python(self):
        total = None if self.epochs < 0 else self.epochs
        epoch = 0
        while total is None or epoch < total:
            perm = _epoch_perm(self.n_rows, epoch, self.seed, self.shuffle)
            for b in range(self.batches_per_epoch):
                idx = perm[b * self.batch_size:(b + 1) * self.batch_size]
                idx = idx.astype(np.int64)
                yield {
                    name: self._gather(i, idx)
                    for i, name in enumerate(self.names)
                }
            epoch += 1

    def _iter_native(self):
        lib = self._lib
        h = lib.ad_loader_create(
            len(self.sources), self.n_rows, self.batch_size, self.capacity,
            self.num_threads, int(self.shuffle), self.seed,
            int(self.drop_remainder), self.epochs,
        )
        if not h:
            logging.warning("native loader create failed; falling back to python")
            yield from self._iter_python()
            return
        try:
            for i, shards in enumerate(self.sources):
                head = shards[0]
                row_bytes = head.dtype.itemsize * int(
                    np.prod(head.shape[1:], dtype=np.int64))
                if len(shards) == 1:
                    lib.ad_loader_set_source(
                        h, i, head.ctypes.data_as(ctypes.c_void_p), row_bytes
                    )
                else:
                    bases = (ctypes.c_void_p * len(shards))(
                        *[s.ctypes.data_as(ctypes.c_void_p).value for s in shards]
                    )
                    srows = (ctypes.c_uint64 * len(shards))(
                        *[s.shape[0] for s in shards]
                    )
                    rc = lib.ad_loader_set_source_shards(
                        h, i, bases, srows, len(shards), row_bytes
                    )
                    if rc != 0:
                        raise RuntimeError(
                            f"native loader rejected shard table for "
                            f"{self.names[i]!r}"
                        )
            if lib.ad_loader_start(h) != 0:
                raise RuntimeError("native loader failed to start")
            ptrs = (ctypes.c_void_p * len(self.sources))()
            rows = ctypes.c_uint64()
            while True:
                slot = lib.ad_loader_next(h, ptrs, ctypes.byref(rows))
                if slot < 0:
                    break
                n = int(rows.value)
                batch = {}
                for i, name in enumerate(self.names):
                    head = self.sources[i][0]
                    shape = (n,) + head.shape[1:]
                    nbytes = head.dtype.itemsize * int(np.prod(shape, dtype=np.int64))
                    # bytearray copy: (a) frees the slot for immediate refill,
                    # (b) yields a WRITEABLE array like the python engine's
                    # fancy-indexed copies (np.frombuffer over bytes would be
                    # read-only and break in-place batch mutation).
                    buf = bytearray(ctypes.string_at(ptrs[i], nbytes))
                    batch[name] = np.frombuffer(buf, dtype=head.dtype).reshape(shape)
                lib.ad_loader_release(h, int(slot))
                yield batch
        finally:
            lib.ad_loader_destroy(h)
