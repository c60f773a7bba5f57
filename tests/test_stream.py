"""Streaming data path: event re-batching, chunk stacking, the bounded
``ChunkStream`` worker (shutdown + error re-raise), the file-tail source,
the once-per-process tail-drop note, and ``train_ctr(mode="stream")``
end-to-end with both engines.

The contract under test (docs/streaming.md): events of any length are
re-batched into exact ``batch_size`` batches with rows carried across
event boundaries, stacked into the same ``[k, batch, ...]`` chunks the
epoch prefetcher emits, and fed through a bounded queue whose close/error
semantics mirror ``data.prefetch.prefetch``.
"""

import logging
import threading
import time

import numpy as np
import pytest

from repro.data import synthetic
from repro.data.stream import (
    ChunkStream,
    batches_from_events,
    chunks_from_batches,
    follow_tsv_events,
    stream_chunks,
    synthetic_event_stream,
    write_tsv_rows,
)
from repro.data.synthetic import make_ctr_dataset

VOCABS = (60, 13, 5)


def _events_from(ds, sizes):
    start = 0
    for n in sizes:
        idx = np.arange(start, start + n)
        yield {"ids": ds.ids[idx], "dense": ds.dense[idx],
               "labels": ds.labels[idx]}
        start += n


def _reset_tail_note():
    synthetic._tail_note_fired = False
    synthetic._noted_remainders.clear()


# ---------------------------------------------------------------------------
# re-batching and stacking
# ---------------------------------------------------------------------------


def test_rebatch_carries_rows_across_events():
    """Odd-sized events re-batch into exact batches with no row lost or
    reordered before the final sub-batch tail."""
    ds = make_ctr_dataset(100, VOCABS, n_dense=3, seed=0)
    sizes = [7, 1, 30, 0, 13, 49]          # 100 rows, incl. an empty event
    out = list(batches_from_events(_events_from(ds, sizes), 16))
    assert len(out) == 100 // 16
    for b in out:
        assert b["ids"].shape == (16, 3)
        assert b["dense"].shape == (16, 3)
        assert b["labels"].shape == (16,)
    got = np.concatenate([b["labels"] for b in out])
    np.testing.assert_array_equal(got, ds.labels[:96])


def test_rebatch_requires_drop_remainder():
    ds = make_ctr_dataset(20, VOCABS, n_dense=3, seed=1)
    with pytest.raises(ValueError, match="drop_remainder"):
        list(batches_from_events(_events_from(ds, [20]), 16,
                                 drop_remainder=False))
    with pytest.raises(ValueError, match="batch_size"):
        list(batches_from_events(_events_from(ds, [20]), 0))


def test_chunk_stacking_shapes():
    ds = make_ctr_dataset(160, VOCABS, n_dense=3, seed=2)
    batches = batches_from_events(_events_from(ds, [160]), 16)
    chunks = list(chunks_from_batches(batches, scan_steps=4))
    # 10 batches -> [4, 4, 2]
    assert [c["labels"].shape[0] for c in chunks] == [4, 4, 2]
    for c in chunks:
        assert c["ids"].shape[1:] == (16, 3)
    got = np.concatenate([c["labels"].reshape(-1) for c in chunks])
    np.testing.assert_array_equal(got, ds.labels[:160])


# ---------------------------------------------------------------------------
# the once-per-process tail note
# ---------------------------------------------------------------------------


def test_tail_note_fires_once_per_process(caplog):
    """A stream re-opens its source repeatedly, so every re-open presents a
    fresh (n, batch) pair — the note must fire once per process, not once
    per shape."""
    _reset_tail_note()
    ds = make_ctr_dataset(50, VOCABS, n_dense=3, seed=3)
    with caplog.at_level(logging.WARNING, logger="repro.data.synthetic"):
        list(batches_from_events(_events_from(ds, [45]), 16))   # 13-row tail
        list(batches_from_events(_events_from(ds, [50]), 32))   # 18-row tail
    notes = [r for r in caplog.records if "dropping" in r.getMessage()]
    assert len(notes) == 1
    # both shapes are still recorded for introspection
    assert {(45, 16), (50, 32)} <= synthetic._noted_remainders
    _reset_tail_note()


# ---------------------------------------------------------------------------
# ChunkStream: bounded queue, shutdown, error re-raise
# ---------------------------------------------------------------------------


def test_chunk_stream_delivers_everything_in_order():
    ds = make_ctr_dataset(128, VOCABS, n_dense=3, seed=4)
    with stream_chunks(_events_from(ds, [50, 50, 28]), 16, 2,
                       buffer_size=2) as cs:
        chunks = list(cs)
    assert [c["labels"].shape[0] for c in chunks] == [2, 2, 2, 2]
    got = np.concatenate([c["labels"].reshape(-1) for c in chunks])
    np.testing.assert_array_equal(got, ds.labels[:128])


def test_chunk_stream_reraises_worker_error():
    ds = make_ctr_dataset(64, VOCABS, n_dense=3, seed=5)

    def bad_events():
        yield from _events_from(ds, [32])
        raise RuntimeError("source fell over")

    cs = ChunkStream(bad_events(), 16, 1)
    with pytest.raises(RuntimeError, match="source fell over"):
        list(cs)


def test_chunk_stream_close_stops_blocked_worker():
    """A consumer that walks away mid-stream must not leave the worker
    spinning: close() unblocks the bounded-queue put and closes the source
    generator."""
    ds = make_ctr_dataset(64, VOCABS, n_dense=3, seed=6)
    closed = threading.Event()

    def endless():
        try:
            while True:
                yield {"ids": ds.ids[:8], "dense": ds.dense[:8],
                       "labels": ds.labels[:8]}
        finally:
            closed.set()

    cs = ChunkStream(endless(), 8, 1, buffer_size=1)
    it = iter(cs)
    next(it)                     # worker is now blocked on the full queue
    cs.close()
    cs._worker.join(timeout=5.0)
    assert not cs._worker.is_alive()
    assert closed.wait(timeout=1.0)
    cs.close()                   # idempotent


def test_synthetic_event_stream_bounded_and_reshuffled():
    ds = make_ctr_dataset(40, VOCABS, n_dense=3, seed=7)
    evs = list(synthetic_event_stream(ds, events=5, rows_per_event=16,
                                      seed=0))
    assert len(evs) == 5
    # 3 events per 40-row pass: the second pass reshuffles
    first_pass = np.concatenate([e["labels"] for e in evs[:3]])
    np.testing.assert_array_equal(np.sort(first_pass), np.sort(ds.labels))
    # deterministic: the same seed replays the same stream
    evs2 = list(synthetic_event_stream(ds, events=5, rows_per_event=16,
                                       seed=0))
    for a, b in zip(evs, evs2):
        np.testing.assert_array_equal(a["ids"], b["ids"])


# ---------------------------------------------------------------------------
# file-tail source
# ---------------------------------------------------------------------------


def test_follow_tsv_roundtrip(tmp_path):
    ds = make_ctr_dataset(48, VOCABS, n_dense=3, seed=8)
    path = str(tmp_path / "events.tsv")
    open(path, "w").close()

    def produce():
        write_tsv_rows(path, ds, 0, 20)
        time.sleep(0.05)
        write_tsv_rows(path, ds, 20, 48)

    t = threading.Thread(target=produce)
    t.start()
    evs = list(follow_tsv_events(path, VOCABS, 3, rows_per_event=16,
                                 idle_timeout_s=0.5))
    t.join()
    assert sum(len(e["labels"]) for e in evs) == 48
    got_ids = np.concatenate([e["ids"] for e in evs])
    np.testing.assert_array_equal(got_ids, ds.ids)
    got_dense = np.concatenate([e["dense"] for e in evs])
    np.testing.assert_allclose(got_dense, ds.dense, atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------
# train_ctr(mode="stream") end to end
# ---------------------------------------------------------------------------


def _stream_cfg_hp():
    from repro.core import scale_hyperparams
    from repro.models import ctr

    cfg = ctr.CTRConfig(name="deepfm", vocab_sizes=VOCABS, n_dense=3,
                        emb_dim=8, mlp_dims=(16, 16, 16), emb_sigma=1e-2,
                        placement="hotcold")
    hp = scale_hyperparams("cowclip", base_lr=1e-3, base_l2=1e-3,
                           base_batch=32, batch_size=32, base_dense_lr=2e-3)
    return cfg, hp


def _run_stream(engine, max_steps=12):
    import jax

    from repro.core import build_train_step
    from repro.train import train_ctr

    cfg, hp = _stream_cfg_hp()
    ds = make_ctr_dataset(600, VOCABS, n_dense=3, zipf_a=1.2, seed=9)
    tr, te = ds.split(0.8)
    bundle = build_train_step(cfg, hp, hot_capacity=16)
    stream = stream_chunks(
        synthetic_event_stream(tr, events=40, rows_per_event=48, seed=1),
        32, 4)
    res = train_ctr(cfg, None, tr, te, batch_size=32, seed=0,
                    step_bundle=bundle, engine=engine, mode="stream",
                    stream=stream, max_steps=max_steps)
    return res, jax.tree.leaves(bundle.export(res.params))


def test_stream_training_eager_scan_agree():
    """The same event stream through the eager and scan engines: identical
    step count and final params (the scan body is the same jitted step)."""
    res_e, leaves_e = _run_stream("eager")
    res_s, leaves_s = _run_stream("scan")
    assert res_e.steps == res_s.steps == 12
    assert np.isfinite(res_e.final_eval["logloss"])
    assert 0.0 <= res_e.final_eval["auc"] <= 1.0
    for a, b in zip(leaves_e, leaves_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=0)
