"""Double-buffered background prefetch for the compiled training engine.

The engine's scan-fused step (repro.train.engine) consumes *chunks* — K
batches stacked into one ``[K, batch, ...]`` host array per field — so a
single dispatch covers K optimizer steps. This module owns the host side of
that contract:

* ``chunk_epoch`` — one epoch of stacked chunks as contiguous NumPy arrays,
  built with **exactly** the same shuffle order and remainder semantics as
  ``synthetic.iterate_batches`` (same seed => same batches in the same
  order, so the scan engine is bit-equivalent to the eager loop).
* ``prefetch`` — runs any host iterator on a worker thread and keeps one
  chunk ahead resident on device: while the consumer computes chunk *i*,
  the worker stacks chunk *i+1* into contiguous host memory and the
  generator has already issued its ``jax.device_put``. On accelerators the
  copy overlaps compute (contiguous host arrays are the closest CPython
  gets to pinned staging buffers); on CPU it still hides the NumPy
  gather/stack cost behind the running step.
* ``prefetch_chunks`` — the composition the train loop uses.

The worker is a daemon thread behind a bounded queue (default 2 chunks —
double buffering; deeper buffers only add host RAM). Closing the generator
early (``max_steps``, errors) stops the worker promptly; worker exceptions
re-raise in the consumer.

Under ``jax.profiler.trace`` the feed writes three host spans per chunk,
each with the chunk's sequence number as its ``chunk`` argument:
``prefetch.stack`` (the worker, around ``next`` of the host iterator),
``prefetch.wait`` (the consumer, around taking the chunk off the queue) and
``prefetch.put`` (the consumer, around its ``jax.device_put``). With no
trace running each costs one check.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Iterator, Optional

import jax
import numpy as np

from .synthetic import CTRDataset, note_dropped_remainder

_DONE = object()
_span = jax.profiler.TraceAnnotation


def chunk_epoch(
    ds: CTRDataset,
    batch_size: int,
    scan_steps: int,
    *,
    shuffle: bool = True,
    seed: int = 0,
    drop_remainder: bool = True,
) -> Iterator[dict]:
    """One epoch of ``[k, batch_size, ...]`` stacked chunks (host arrays).

    ``k == scan_steps`` except possibly for the epoch's final chunk, which
    carries the leftover ``k < scan_steps`` batches (never dropped — only
    the sub-``batch_size`` row tail follows ``drop_remainder``, exactly as
    in ``iterate_batches``). One fancy-index per chunk gathers all ``k``
    batches at once, then a reshape lays them out ``[k, batch, ...]``
    contiguously.
    """
    if scan_steps < 1:
        raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
    n = len(ds)
    order = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(order)
    if drop_remainder:
        note_dropped_remainder(n, batch_size)
    n_batches = n // batch_size if drop_remainder else -(-n // batch_size)
    if not drop_remainder and n % batch_size:
        # the engine's scanned body needs static [batch_size] shapes; a
        # short row tail cannot join a chunk
        raise ValueError(
            "chunk_epoch requires drop_remainder=True (the scanned step "
            f"needs static batch shapes; {n % batch_size} tail rows do not "
            "fill a batch)")
    for start in range(0, n_batches, scan_steps):
        k = min(scan_steps, n_batches - start)
        idx = order[start * batch_size:(start + k) * batch_size]
        yield {
            "ids": ds.ids[idx].reshape(k, batch_size, -1),
            "dense": ds.dense[idx].reshape(k, batch_size, -1),
            "labels": ds.labels[idx].reshape(k, batch_size),
        }


def prefetch(host_iter, *, buffer_size: int = 2, to_device: bool = True):
    """Drive ``host_iter`` on a worker thread, staying one item ahead.

    Yields items in order. With ``to_device`` each item is ``device_put``
    *before* the previous one is yielded, so the next chunk's host->device
    copy is in flight while the consumer computes — the double-buffer
    contract. Worker exceptions surface in the consumer; closing the
    generator stops the worker.
    """
    q: queue.Queue = queue.Queue(maxsize=max(1, buffer_size))
    stop = threading.Event()
    failure: list = []

    def work():
        try:
            items = iter(host_iter)
            for seq in itertools.count():
                with _span("prefetch.stack", chunk=seq):
                    item = next(items, _DONE)
                if item is _DONE:
                    break
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # re-raised in the consumer
            failure.append(e)
        finally:
            while not stop.is_set():
                try:
                    q.put(_DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    worker = threading.Thread(target=work, daemon=True, name="repro-prefetch")
    worker.start()
    pending = None
    try:
        for seq in itertools.count():
            with _span("prefetch.wait", chunk=seq):
                item = q.get()
            if item is _DONE:
                break
            staged = item
            if to_device:
                with _span("prefetch.put", chunk=seq):
                    staged = jax.device_put(item)
            if pending is not None:
                yield pending
            pending = staged
        if failure:
            raise failure[0]
        if pending is not None:
            yield pending
    finally:
        stop.set()
        # unblock a worker stuck on a full queue
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break


def prefetch_chunks(
    ds: CTRDataset,
    batch_size: int,
    scan_steps: int,
    *,
    shuffle: bool = True,
    seed: int = 0,
    drop_remainder: bool = True,
    buffer_size: int = 2,
) -> Iterator[dict]:
    """One epoch of device-resident ``[k, batch, ...]`` chunks, stacked on a
    background thread and copied ahead of consumption (the engine's input
    pipeline)."""
    return prefetch(
        chunk_epoch(ds, batch_size, scan_steps, shuffle=shuffle, seed=seed,
                    drop_remainder=drop_remainder),
        buffer_size=buffer_size)
