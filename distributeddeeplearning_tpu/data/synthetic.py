"""Seeded synthetic dataset with virtual length — the universal fake backend.

Capability parity with the reference's ``FAKE=True`` mode, its de-facto
test/benchmark infrastructure (SURVEY.md §4.1): a small *physical* pool of
seeded random batches indexed through a random ``translation_index`` of
*virtual* length N, giving realistic epoch size without disk. Reference
implementations: TF ``_create_fake_data_fn`` (``imagenet_estimator_tf_
horovod.py:295-345``, seed 42 at ``:284-287``), Keras ``FakeDataGenerator``
(``HorovodKeras/src/data_generator.py:22-53``, pool of 20 batches,
translation index at ``:45,52``), PyTorch ``FakeData``
(``imagenet_pytorch_horovod.py:146-191``).

TPU-first differences: NHWC layout (XLA:TPU's preferred conv layout, vs
the reference's NCHW-for-cuDNN), per-process sharding built in (each host
yields only its slice of the global batch, the ``DistributedSampler``
equivalent — reference PyTorch ``:258-264``), and batches are yielded as
numpy for zero-copy ``device_put``.
"""

from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np


def _check_divisible(global_batch_size: int, process_count: int) -> None:
    if global_batch_size % process_count != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by "
            f"{process_count} processes"
        )


def _virtual_translation(
    seed: int, process_index: int, pool_n: int, local_len: int
) -> Tuple[int, np.ndarray]:
    """The virtual→physical translation-index contract shared by every
    synthetic dataset (reference ``data_generator.py:45``): a per-process
    seed offset so hosts draw disjoint streams, sized to the local share
    of the virtual length."""
    idx_seed = (seed + 1 + process_index) % (2**31 - 1)
    translation = np.random.RandomState(idx_seed).randint(
        0, pool_n, size=(max(local_len, 1),)
    )
    return idx_seed, translation


def _check_topology(topology: str) -> str:
    if topology not in ("process", "global"):
        raise ValueError(
            f"data topology must be 'process' or 'global', got {topology!r}"
        )
    return topology


def _epoch_permutation(
    idx_seed: int, translation: np.ndarray, epoch_index: int
) -> np.ndarray:
    """Deterministic per-epoch reshuffle (Keras ``_set_index_array``
    parity), identical across the dataset types."""
    return np.random.RandomState(
        (idx_seed + 7919 * epoch_index) % (2**31 - 1)
    ).permutation(translation)


def _image_pool(
    pool_n: int, sample_shape: Tuple[int, ...], seed: int, dtype: np.dtype
) -> np.ndarray:
    """The physical image pool in its staging dtype, deterministic in
    ``seed`` alone.

    Filled through the native threaded counter-mode fill
    (native/ddl_native.cc; the numpy fallback is bit-identical — the
    pool is GBs at bench batch sizes and RandomState.uniform is
    single-threaded), a slab of samples at a time: ``out[i]`` depends
    only on ``seed + i``, so slabs reproduce the one-shot fill bit for
    bit while peak host memory is the pool in ``dtype`` plus one slab,
    not several float32 copies of the whole pool (3.1 GB each at
    b=256 and 224 px, 12.3 GB at global batch 1,024)."""
    from distributeddeeplearning_tpu.native import fill_uniform

    out = np.empty((pool_n,) + tuple(sample_shape), dtype)
    per_sample = int(np.prod(sample_shape))
    slab = max(1, (256 << 20) // (4 * per_sample))  # ~256 MiB of float32
    for start in range(0, pool_n, slab):
        stop = min(start + slab, pool_n)
        u = fill_uniform(
            (stop - start,) + tuple(sample_shape),
            seed=seed + start * per_sample,
        )
        if dtype == np.uint8:
            # raw-byte staging (INPUT_STAGING=uint8): synthetic pixels in
            # the real datasets' pre-normalization range
            u *= np.float32(255.0)
        else:
            u *= np.float32(2.0)
            u -= np.float32(1.0)
        out[start:stop] = u.astype(dtype, copy=False)
    return out


class SyntheticImageDataset:
    """Seeded random images + labels with a virtual length.

    Parameters mirror the reference contract: ``length`` is the virtual
    dataset size (``FAKE_DATA_LENGTH``, default 1,281,167 = ImageNet),
    ``num_physical_batches`` the real pool size (reference uses 20,
    ``data_generator.py:30``).
    """

    def __init__(
        self,
        *,
        length: int = 1_281_167,
        global_batch_size: int,
        image_size: int = 224,
        num_classes: int = 1000,
        channels: int = 3,
        num_physical_batches: int = 20,
        seed: int = 42,
        process_index: int = 0,
        process_count: int = 1,
        one_hot: bool = False,
        exact: bool = False,
        dtype: np.dtype = np.float32,
        topology: str = "process",
    ):
        _check_divisible(global_batch_size, process_count)
        self.length = length
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // process_count
        self.image_size = image_size
        self.num_classes = num_classes
        self.one_hot = one_hot
        self.process_index = process_index
        self.process_count = process_count
        # topology="global" (DATA_TOPOLOGY, docs/DATA.md): ONE
        # process-count-independent stream — pool and translation index
        # are seeded/sized from the GLOBAL batch and each process takes
        # its contiguous slice of every global batch, so the delivered
        # global batch is identical at any world size (what elastic
        # shrink/grow needs to preserve the math). "process" keeps the
        # reference's disjoint per-process streams.
        self.topology = _check_topology(topology)

        rng = np.random.RandomState(seed)  # seed 42 parity (TF :284-287)
        pool_batch = (
            global_batch_size if self.topology == "global"
            else self.local_batch_size
        )
        pool_n = num_physical_batches * pool_batch
        self._images = _image_pool(
            pool_n, (image_size, image_size, channels), seed, np.dtype(dtype)
        )
        self._labels = rng.randint(0, num_classes, size=(pool_n,)).astype(np.int32)
        # Virtual→physical translation index (reference data_generator.py:45).
        # Sized to the *local* share of the virtual length; offset by process
        # index so hosts draw disjoint streams (DistributedSampler parity).
        # exact=True (validation): ceil instead of floor/truncate — every
        # virtual sample is served exactly once, with the trailing partial
        # batch padded and zero-weighted.
        self.exact = exact
        if self.topology == "global":
            # One global translation index, identical on every process
            # (seed offset 0, sized to the full virtual length); the
            # per-process share is a slice taken per batch in epoch().
            self.steps_per_epoch = (
                -(-length // global_batch_size) if exact
                else max(length // global_batch_size, 1)
            )
            self._idx_seed, self._translation_index = _virtual_translation(
                seed, 0, pool_n, length
            )
            self._local_len = length
        elif exact:
            local_len = (length - process_index + process_count - 1) // process_count
            self.steps_per_epoch = -(-length // global_batch_size)
            self._idx_seed, self._translation_index = _virtual_translation(
                seed, process_index, pool_n, local_len
            )
            self._local_len = local_len
        else:
            local_len = length // process_count
            self.steps_per_epoch = max(length // global_batch_size, 1)
            self._idx_seed, self._translation_index = _virtual_translation(
                seed, process_index, pool_n, local_len
            )
            self._local_len = local_len

    def __len__(self) -> int:
        return self.length

    def epoch(self, epoch_index: int = 0) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield ``steps_per_epoch`` local batches ``(images, labels)``.

        Deterministic in ``(seed, epoch_index, process_index)`` — the
        reference reshuffles its index each epoch (Keras
        ``_set_index_array``); we deterministically re-permute the
        translation index per epoch.
        """
        b = self.local_batch_size
        index = _epoch_permutation(self._idx_seed, self._translation_index, epoch_index)
        for step in range(self.steps_per_epoch):
            if self.topology == "global":
                # This process's contiguous slice of the GLOBAL batch:
                # concatenated over processes (mesh order), every world
                # size delivers the same global batch.
                start = step * self.global_batch_size + self.process_index * b
            else:
                start = step * b
            slots = np.arange(start, start + b)
            sel = index[slots % len(index)]
            images = self._images[sel]
            labels = self._labels[sel]
            if self.one_hot:
                labels = np.eye(self.num_classes, dtype=np.float32)[labels]
            if self.exact:
                # weight 0 on padded slots past this process's share
                # (global topology: past the global virtual length)
                weights = (slots < self._local_len).astype(np.float32)
                yield images, labels, weights
            else:
                yield images, labels

    def __iter__(self):
        return self.epoch(0)


class SyntheticTokenDataset:
    """Seeded random token stream for LM training — the ``FAKE=True``
    contract (SURVEY.md §4.1), token edition.

    Same virtual-length trick as :class:`SyntheticImageDataset`: a small
    physical pool of ``[seq_len+1]`` token rows indexed through a
    seeded translation index, yielding ``(tokens[:, :-1], tokens[:, 1:])``
    next-token pairs, per-process sharded.
    """

    def __init__(
        self,
        *,
        length: int = 100_000,
        global_batch_size: int,
        seq_len: int = 128,
        vocab_size: int = 32_000,
        num_physical_batches: int = 20,
        seed: int = 42,
        process_index: int = 0,
        process_count: int = 1,
        topology: str = "process",
    ):
        _check_divisible(global_batch_size, process_count)
        self.length = length
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // process_count
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.process_index = process_index
        self.process_count = process_count
        self.topology = _check_topology(topology)

        rng = np.random.RandomState(seed)
        if self.topology == "global":
            # Process-count-independent stream (see the image dataset).
            pool_n = num_physical_batches * global_batch_size
            idx_args = (seed, 0, pool_n, length)
        else:
            pool_n = num_physical_batches * self.local_batch_size
            idx_args = (seed, process_index, pool_n, length // process_count)
        self._rows = rng.randint(
            0, vocab_size, size=(pool_n, seq_len + 1)
        ).astype(np.int32)
        self._idx_seed, self._translation_index = _virtual_translation(
            *idx_args
        )
        self.steps_per_epoch = max(length // global_batch_size, 1)

    def __len__(self) -> int:
        return self.length

    def epoch(self, epoch_index: int = 0) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        b = self.local_batch_size
        index = _epoch_permutation(self._idx_seed, self._translation_index, epoch_index)
        for step in range(self.steps_per_epoch):
            if self.topology == "global":
                start = step * self.global_batch_size + self.process_index * b
            else:
                start = step * b
            sel = index[np.arange(start, start + b) % len(index)]
            rows = self._rows[sel]
            yield rows[:, :-1], rows[:, 1:]

    def __iter__(self):
        return self.epoch(0)
