"""Paper T6 (+T9): partial tensor transfers and command batching on the
host->device input path.

On TPU the device-to-device path is ICI collectives (T9 comes for free),
but feature ingestion still crosses host->device. The paper's two tricks
apply directly:

- *Partial tensor transfers*: sparse-index tensors are compiled at a static
  maximum size, but only the lookups inside each bag are transferred,
  padded to a width bucket.
- *Command batching*: many small per-table index vectors are coalesced into
  one staging buffer and shipped as a single transfer.

``staged_transfer`` ships the staging buffer, ``(B, W)`` and item-major:
row b holds item b's bags back to back in table order, then zeros, and
``W`` is a width bucket (the next power of two of the longest item's
lookups, at most ``T * Lmax``). ``models/dlrm.py``'s ``sls_forward``
gathers straight from it. ``command_batched_transfer`` also unpacks it on
the device into the static ``(B, T, Lmax)`` layout, one compiled program
per shape and width bucket. Where each bag starts is data: both derive it
from the lengths they are given, so requests with new bag lengths reuse
what is compiled.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class TransferStats:
    bytes_full: int = 0          # what a naive full-size transfer would ship
    bytes_partial: int = 0       # the used prefixes shipped (and lengths)
    bytes_padding: int = 0       # staged columns past the used prefixes,
                                 # shipped to fill the width bucket
    num_transfers_naive: int = 0
    num_transfers_batched: int = 0
    rows_gathered: int = 0       # index slots the sparse stage gathers:
                                 # B x W staged, B x T x Lmax padded
    rows_live: int = 0           # lookups inside a bag (sum of lengths)

    @property
    def bytes_saved_frac(self) -> float:
        return 1.0 - self.bytes_partial / max(self.bytes_full, 1)

    @property
    def live_share(self) -> float:
        """The share of the gathered index slots that are lookups."""
        return self.rows_live / max(self.rows_gathered, 1)


@dataclass
class SparseBatch:
    """Static-shape SLS inputs for one request batch.

    indices (B, T, Lmax) int32, lengths (B, T) int32 — per-sample bags per
    table, padded to the compile-time max ``Lmax``.
    """
    indices: np.ndarray
    lengths: np.ndarray


def pack_sparse_inputs(bags: Sequence[Sequence[Sequence[int]]],
                       num_tables: int, max_lookups: int) -> SparseBatch:
    """bags[b][t] = list of indices for sample b, table t."""
    B = len(bags)
    idx = np.zeros((B, num_tables, max_lookups), np.int32)
    lens = np.zeros((B, num_tables), np.int32)
    for b, sample in enumerate(bags):
        for t, bag in enumerate(sample):
            L = min(len(bag), max_lookups)
            idx[b, t, :L] = np.asarray(bag[:L], np.int32)
            lens[b, t] = L
    return SparseBatch(idx, lens)


def staged_width(columns: int, cap: int) -> int:
    """The width bucket that ``columns`` staged columns ship in: the next
    power of two, at most ``cap``."""
    return min(1 << max(columns - 1, 0).bit_length(), cap)


def width_buckets(cap: int) -> Tuple[int, ...]:
    """Every staged width ``staged_width`` gives for a cap."""
    return tuple(sorted({staged_width(c, cap) for c in
                         [1 << k for k in range(cap.bit_length())] + [cap]}))


@functools.partial(jax.jit, static_argnames=("max_lookups",))
def unpack_indices(staged, lengths, max_lookups: int):
    """staged (B, W): each item's bags back to back in table order, then
    padding; lengths (B, T) -> indices (B, T, max_lookups), zero past each
    bag."""
    B, T = lengths.shape
    start = jnp.cumsum(lengths, axis=1) - lengths               # (B, T)
    pos = jnp.arange(max_lookups)
    src = jnp.minimum(start[..., None] + pos, staged.shape[1] - 1)
    vals = jnp.take_along_axis(staged, src.reshape(B, -1), axis=1)
    return jnp.where(pos < lengths[..., None], vals.reshape(src.shape), 0)


def _item_major(indices: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """(B, T, L) bags -> (B, W) int32: each item's bags back to back in
    table order, then zeros to the width bucket of the longest item. Row b
    is T + 1 runs of consecutive reads, its bags and then its padding, so
    slot k of the flat buffer reads ``k`` plus its run's (first read -
    first slot)."""
    B, T, L = indices.shape
    totals = lengths.sum(axis=1)
    width = staged_width(int(totals.max()), T * L)
    runs = np.empty((B, T + 1), np.intp)
    runs[:, :T] = lengths
    runs[:, T] = width - totals
    first = np.zeros((B, T + 1), np.intp)      # padding reads from 0 ...
    first[:, :T] = np.arange(0, B * T * L, L).reshape(B, T)
    runs = runs.ravel()
    src = np.repeat(first.ravel() - (np.cumsum(runs) - runs), runs)
    src += np.arange(B * width)
    staged = indices.reshape(-1).take(src).reshape(B, width)
    staged *= np.arange(width) < totals[:, None]            # ... and is zeroed
    return staged.astype(np.int32, copy=False)


def staged_transfer(batch: SparseBatch,
                    stats: Optional[TransferStats] = None,
                    device=None) -> Tuple[jax.Array, jax.Array]:
    """Coalesce every bag's lookups into ONE staging buffer and make a
    single host->device put (command batching).

    Returns (staged (B, W) on device: row b holds item b's bags back to
    back, table 0 first, then zeros to the width bucket of the longest
    item; lengths (B, T) on device).
    """
    B, T, Lmax = batch.indices.shape
    lengths = batch.lengths
    if lengths.size and (lengths.min() < 0 or lengths.max() > Lmax):
        raise ValueError(f"bag lengths must lie in [0, {Lmax}]")
    # partial transfer: ship only the lookups inside each bag, item by
    # item, in a staging buffer padded to its width bucket
    staged = _item_major(batch.indices, lengths)
    if stats is not None:
        live = int(lengths.sum())
        stats.bytes_full += batch.indices.nbytes + lengths.nbytes
        stats.bytes_partial += live * staged.itemsize + lengths.nbytes
        stats.bytes_padding += staged.nbytes - live * staged.itemsize
        stats.num_transfers_naive += T + 1          # one per table + lengths
        stats.num_transfers_batched += 2            # staged + lengths
        stats.rows_gathered += staged.size
        stats.rows_live += live
    return jax.device_put(staged, device), jax.device_put(lengths, device)


def command_batched_transfer(batch: SparseBatch,
                             stats: Optional[TransferStats] = None,
                             device=None) -> Tuple[jax.Array, jax.Array]:
    """``staged_transfer``, then unpack into the static layout on device
    with ``unpack_indices``.

    Returns (indices (B,T,Lmax) on device, lengths (B,T) on device).
    """
    staged_dev, lens_dev = staged_transfer(batch, stats, device)
    if stats is not None:           # the unpacked layout is gathered whole
        stats.rows_gathered += batch.indices.size - staged_dev.size
    return unpack_indices(staged_dev, lens_dev,
                          batch.indices.shape[2]), lens_dev


def snapshot_device_get(tree, stats: Optional[TransferStats] = None,
                        full_bytes: Optional[int] = None):
    """Device->host leg of the sequence-snapshot path (PR 8): ship an
    arbitrary pytree of device rows to host numpy in ONE batched
    ``device_get`` (command batching — one sync for all leaves, not one
    per cache leaf). ``full_bytes`` is what naive whole-row extraction
    would have shipped; the difference is the partial-transfer saving
    from slicing positional leaves to the written prefix. Returns the
    host tree; stats accounting mirrors ``command_batched_transfer``."""
    host = jax.device_get(tree)
    if stats is not None:
        leaves = jax.tree.leaves(host)
        partial = sum(np.asarray(x).nbytes for x in leaves)
        stats.bytes_partial += partial
        stats.bytes_full += full_bytes if full_bytes is not None else partial
        stats.num_transfers_naive += len(leaves)
        stats.num_transfers_batched += 1
    return host


def snapshot_device_put(tree, stats: Optional[TransferStats] = None,
                        device=None):
    """Host->device leg of snapshot restore: one batched ``device_put``
    of the zero-padded row tree (the device-side slot scatter is the
    engine's existing donated slot-write executable). The restore ships
    full rows — the padding is the price of the static slot layout — so
    partial == full here; the saving was taken on the snapshot leg."""
    dev = jax.device_put(tree, device)
    if stats is not None:
        leaves = jax.tree.leaves(tree)
        nbytes = sum(np.asarray(x).nbytes for x in leaves)
        stats.bytes_partial += nbytes
        stats.bytes_full += nbytes
        stats.num_transfers_naive += len(leaves)
        stats.num_transfers_batched += 1
    return dev


def naive_transfer(batch: SparseBatch,
                   stats: Optional[TransferStats] = None,
                   device=None) -> Tuple[jax.Array, jax.Array]:
    """Baseline: ship every table's full static-size tensor separately."""
    if stats is not None:
        stats.bytes_full += batch.indices.nbytes + batch.lengths.nbytes
        stats.bytes_partial += batch.indices.nbytes + batch.lengths.nbytes
        stats.num_transfers_naive += batch.indices.shape[1] + 1
        stats.num_transfers_batched += batch.indices.shape[1] + 1
        stats.rows_gathered += batch.indices.size
        stats.rows_live += int(batch.lengths.sum())
    return (jax.device_put(jnp.asarray(batch.indices), device),
            jax.device_put(jnp.asarray(batch.lengths), device))
