"""Compiled multi-step training engine: scan-fused steps + async prefetch.

The paper's wall-clock claim (12h -> 10min) is about removing every
per-step overhead *around* the large-batch update: once CowClip makes the
128K batch trainable, the limiter is dispatch latency, the host->device
copy, and fp32 bandwidth — not math. The eager ``train_ctr`` loop pays all
three per step: one jit dispatch, one blocking ``jnp.asarray`` per batch,
and a fresh output allocation for every table-sized buffer. This module is
the compiled alternative:

* ``make_chunk_runner`` wraps a placement's **pure** scan-compatible step
  (``TrainStepBundle.scan_step``) in a ``lax.scan`` over a ``[k, batch,
  ...]`` chunk with the ``(params, opt_state)`` carry donated — one
  dispatch covers ``k`` optimizer steps, XLA keeps the carry in place
  across iterations (the scatter of step *i* overlaps the gather of step
  *i+1* instead of round-tripping through fresh buffers), and the Python
  interpreter leaves the hot path entirely.
* ``run_epoch`` drives one epoch of chunks from the double-buffered
  background prefetcher (``repro.data.prefetch``): the worker thread
  stacks the next K batches into contiguous host arrays and their
  ``device_put`` is issued while the current chunk computes.

Host-side logging that used to live *inside* the step (the
``sharded_sparse`` capacity-overflow warning) cannot sit in a scanned body
without forcing a callback per iteration; the runner re-attaches it at
chunk level — one ``lax.cond`` over the summed ``aux["overflow_shards"]``
per chunk, outside the scan.

Equivalence contract: ``chunk_epoch`` replays ``iterate_batches``'s exact
shuffle order, and the scanned body is the same traced function the eager
step jits — K scanned steps bit-match K eager steps (params, opt_state,
and the per-step aux), asserted for every placement in
``tests/test_engine.py``. The eager path stays available
(``train_ctr(..., engine="eager")``) for debugging.
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..data import prefetch as prefetch_lib

logger = logging.getLogger(__name__)

ENGINES = ("eager", "scan")

_STREAM_END = object()


class StreamDriveStats(NamedTuple):
    """What ``drive_planned_stream`` measured: steps dispatched, consumer
    time spent blocked waiting on the stream (the un-hidden migration +
    data-staging cost), and whether every chunk arrived pre-planned (the
    overlap-on path) or had to be planned inline."""

    steps: int
    stall_seconds: float
    planned_ahead: bool


def drive_planned_stream(stream, *, plan: Callable, dispatch: Callable,
                         max_steps: Optional[int] = None) -> StreamDriveStats:
    """Consume a chunk stream whose items may carry migration plans.

    The async hot/cold placement's transform wraps each chunk as a
    ``PlannedChunk`` (``.chunk`` + ``.plans``) on the stream's worker
    thread — planning overlaps the device step of the previous chunk, and
    the consumer's only host work is ``dispatch(plan, batch)`` per step.
    Raw chunks (no transform attached) are planned inline via
    ``plan(batch)`` — the overlap-off reference path, bitwise identical
    because planning order is unchanged.

    ``max_steps`` may cut only *unplanned* chunks: a pre-planned step has
    already advanced the planner and registered write-backs, so dropping
    it would leave eviction handles unfillable — the transform must carry
    the same budget (it ends the stream at the boundary instead).
    """
    n = 0
    stall = 0.0
    inline = False
    saw = False
    it = iter(stream)
    while max_steps is None or n < max_steps:
        t0 = time.perf_counter()
        item = next(it, _STREAM_END)
        stall += time.perf_counter() - t0
        if item is _STREAM_END:
            break
        plans = getattr(item, "plans", None)
        chunk = item.chunk if plans is not None else item
        k = chunk["labels"].shape[0]
        if max_steps is not None and n + k > max_steps:
            if plans is not None:
                raise ValueError(
                    f"stream planned {k} step(s) past max_steps={max_steps};"
                    " build the stream transform with the same step budget")
            k = max_steps - n
            chunk = {kk: v[:k] for kk, v in chunk.items()}
        if plans is None:
            inline = True
            plans = [plan({kk: v[i] for kk, v in chunk.items()})
                     for i in range(k)]
        saw = True
        for i in range(k):
            dispatch(plans[i], {kk: v[i] for kk, v in chunk.items()})
            n += 1
    return StreamDriveStats(n, stall, saw and not inline)


def _warn_overflow_chunk(n, k):
    """Chunk-level capacity-overflow note (jax.debug.callback target): the
    per-step warning cannot live inside the scanned body, so the runner
    reports the summed fallback count once per chunk. stderr via logging —
    bench/test drivers parse stdout."""
    logger.warning(
        "[engine] sharded_sparse unique-capacity overflow on %d "
        "field-shard step(s) within a %d-step scanned chunk; dense "
        "per-shard fallback kept those steps exact but O(rows/shard)",
        int(n), int(k))


def make_chunk_runner(scan_step: Callable, *, donate: bool = True) -> Callable:
    """jit'd ``(params, opt_state, chunk) -> (params, opt_state, aux_stack)``.

    ``chunk`` leaves are ``[k, ...]`` stacked batches; the runner scans
    ``scan_step`` over them with the ``(params, opt_state)`` carry donated
    (callers must thread the returned carry and never reuse the arguments).
    ``aux_stack`` mirrors the step's aux dict with a leading ``k`` axis —
    the exactness tests index it per step; reduce it however you like
    (scalars, so host transfer is negligible).

    Re-jits per distinct ``k`` (the epoch-tail chunk and a ``max_steps``
    cut each add at most one compile).
    """

    def run(params, opt_state, chunk):
        def body(carry, batch):
            p, s = carry
            p, s, aux = scan_step(p, s, batch)
            return (p, s), aux

        (params, opt_state), aux = jax.lax.scan(
            body, (params, opt_state), chunk)
        if isinstance(aux, dict) and "overflow_shards" in aux:
            total = jnp.sum(aux["overflow_shards"])
            k = aux["overflow_shards"].shape[0]
            jax.lax.cond(
                total > 0,
                lambda n: jax.debug.callback(_warn_overflow_chunk, n, k),
                lambda n: None, total)
        return params, opt_state, aux

    return jax.jit(run, donate_argnums=(0, 1) if donate else ())


def run_epoch(
    runner: Callable,
    params,
    opt_state,
    ds,
    batch_size: int,
    scan_steps: int,
    *,
    seed: int = 0,
    shuffle: bool = True,
    max_steps: Optional[int] = None,
    buffer_size: int = 2,
    on_chunk: Optional[Callable[[int, dict], None]] = None,
) -> Tuple[object, object, int, Optional[dict]]:
    """One epoch of scan-fused chunks through ``runner``.

    Returns ``(params, opt_state, steps_run, last_aux_stack)``. Respects
    ``max_steps`` (remaining budget for *this* epoch) by slicing the final
    chunk's leading axis — at most one extra compile for the cut shape.
    ``on_chunk(k, aux_stack)`` is called after each dispatch.
    """
    steps_run = 0
    last_aux = None
    chunks = prefetch_lib.prefetch_chunks(
        ds, batch_size, scan_steps, shuffle=shuffle, seed=seed,
        buffer_size=buffer_size)
    for chunk in chunks:
        k = chunk["labels"].shape[0]
        if max_steps is not None and steps_run + k > max_steps:
            k = max_steps - steps_run
            if k <= 0:
                break
            chunk = jax.tree.map(lambda x: x[:k], chunk)
        params, opt_state, last_aux = runner(params, opt_state, chunk)
        if on_chunk is not None:
            on_chunk(k, last_aux)
        steps_run += k
        if max_steps is not None and steps_run >= max_steps:
            break
    return params, opt_state, steps_run, last_aux


def resolve_scan_step(step_bundle, tx_step: Optional[Callable] = None):
    """The scan-compatible body for a bundle (or the tx-path step).

    Every factory in ``repro.train.loop`` attaches its pure, callback-free
    body as ``step.scan_step`` and the bundle carries it as
    ``TrainStepBundle.scan_step``; a jitted step itself also works inside
    ``lax.scan`` (jit-under-jit inlines the trace), so a custom bundle
    without the attribute still runs — minus the chunk-level relocation of
    any host callbacks it embeds.
    """
    if step_bundle is not None:
        if getattr(step_bundle, "scan_step", None) is not None:
            return step_bundle.scan_step
        return getattr(step_bundle.step, "scan_step", step_bundle.step)
    if tx_step is None:
        raise ValueError("need a step bundle or a tx step")
    return getattr(tx_step, "scan_step", tx_step)
