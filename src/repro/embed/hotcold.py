"""Hot/cold two-tier embedding placement for streaming online training.

Terabyte-scale CTR systems (arXiv:2201.05500) keep the full embedding
tables in host memory and train out of a small device-resident cache of
*hot* rows — the Zipf head that appears in nearly every batch. This module
is that placement for the streaming path: a fixed-capacity working set of
hot rows per field, admission/eviction driven by the same cumulative
per-id batch frequencies the serving ``HotEmbeddingCache`` ranks by, with
the full table as the cold backing store for the tail.

The key property is that **residency never changes the math**. A row is
the triple ``(w, m, v)`` plus the ``last_step`` it was last touched at,
and the lazy coupled-L2 decay machinery (core/optim.py) already makes
that pair self-describing: wherever the row lives, the closed-form
catch-up ``w *= (1 - lr*l2)**k`` replays its pending decay on next touch.
Eviction therefore writes back the *raw* row + ``last_step`` — no flush,
no decay settling — and a re-admitted row bit-matches one that stayed hot
the whole time; runs at different capacities are *bitwise identical*
(tests/test_hotcold.py asserts it; capacity 1 is the one exception —
single-row gathers fold to different XLA specializations and land an ulp
off). Each step assembles the batch's
unique rows from whichever tier holds them and then runs exactly the
sparse placement's reference op order (gather -> catch-up ->
forward/backward -> CowClip -> Adam). Against the ``sparse`` placement
itself agreement is to f32 rounding, not bitwise: the two step graphs
fuse differently under XLA, so isolated lanes of the elementwise update
chain can land an ulp apart — far inside the <= 1e-5 tolerance both
placements carry vs the dense substrate.

Admission policy: after each step the hot set becomes the top-``capacity``
ids by batch frequency among {current residents} ∪ {this batch's ids},
ties broken by lower id. Two frequency policies (``admission=``):
``"cumulative"`` counts (the default — frequencies only grow, so the hot
set equals the global top-``capacity`` of all ids touched so far, which
makes the hit rate provably monotone non-decreasing in capacity,
tests/test_hotcold.py), and ``"decayed"`` — counts halved every
``half_life`` steps before each batch is added, so a drifting stream's
stale head ages out. Both are residency- and capacity-independent
(frequency depends only on the batches seen), which is the property the
bitwise capacity-independence test pins down for any policy.

On this container the "device" is CPU-backed, so — as with the serving
cache — the win is architectural rather than wall-clock: the per-step
working set (hot tier + residency maps) is what would live in HBM, and
``benchmarks --stream-bench`` reports those device-resident bytes against
the dense/sparse placements' full tables. The step itself is pure jax
with static shapes, so it jits, scans (``scan_step``), and donates its
carry like every other placement.

Caveats:

* ``state["last_step"]`` (the cold tier's view) is stale for resident
  rows, so ``embed.store.max_pending_depth`` is an *upper bound* here —
  still 0 right after ``flush``, which reconciles both tiers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import optim as optim_lib
from ..core.cowclip import cowclip_rows
from ..models import ctr

__all__ = ["make_hotcold_train_step", "make_migrate_device_step",
           "hot_tier_bytes", "residency_map_bytes", "resident_ids",
           "admission_alpha", "ADMISSIONS"]

ADMISSIONS = ("cumulative", "decayed")


def admission_alpha(admission: str, half_life: int):
    """Per-step frequency decay factor for the admission policy: ``None``
    for cumulative counts, else the f32 ``0.5 ** (1 / half_life)`` both
    the device step and the host planner multiply in before each batch's
    counts (f32 so the two sides stay bitwise in agreement)."""
    if admission not in ADMISSIONS:
        raise ValueError(f"unknown admission policy {admission!r}; "
                         f"expected one of {ADMISSIONS}")
    if admission == "cumulative":
        return None
    if half_life < 1:
        raise ValueError(f"decayed admission needs --half-life >= 1, "
                         f"got {half_life}")
    return np.float32(0.5 ** (1.0 / float(half_life)))


def _top_c_mask(prio_bits, ids, valid, c: int):
    """Exact top-``c`` candidate mask under (priority desc, id asc).

    XLA's CPU sort is a generic single-threaded comparator loop —
    ``lexsort``/``top_k`` over even a few thousand candidates costs
    milliseconds, which dominated the whole hotcold step. The same
    selection falls out of two ~31-iteration binary searches of masked
    O(n) count reductions (microseconds): find the priority threshold
    where the c-th largest sits, then break the tie class by smallest id.
    ``prio_bits`` must be the int32 bitcast of *non-negative* f32
    priorities (bit order == value order there); valid candidate ids are
    unique, so the combined order is strict and the mask selects exactly
    ``min(c, n_valid)`` candidates.
    """
    c = jnp.int32(c)

    def count_gt(x):
        return jnp.sum(valid & (prio_bits > x))

    # smallest threshold t with count(prio > t) < c  ==>  the candidates
    # strictly above t all make the cut and the tie class sits at t
    def prio_step(_, lh):
        lo, hi = lh
        mid = lo + (hi - lo) // 2
        below = count_gt(mid) < c
        return (jnp.where(below, lo, mid + 1), jnp.where(below, mid, hi))

    _, thr = jax.lax.fori_loop(
        0, 31, prio_step, (jnp.int32(0), jnp.int32(2**31 - 1)))
    hi_mask = valid & (prio_bits > thr)
    ties = valid & (prio_bits == thr)
    k = c - jnp.sum(hi_mask)              # >= 1 by choice of thr
    n_eq = jnp.sum(ties)
    k_eff = jnp.minimum(k, jnp.maximum(n_eq, 1))

    # smallest id y with count(tie ids <= y) >= k_eff: the k-th smallest
    # tie id (when n_eq < k — fewer valid candidates than c — every tie
    # is taken and the search result is irrelevant)
    def count_le(y):
        return jnp.sum(ties & (ids <= y))

    def id_step(_, lh):
        lo, hi = lh
        mid = lo + (hi - lo) // 2
        enough = count_le(mid) >= k_eff
        return (jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi))

    _, id_thr = jax.lax.fori_loop(
        0, 31, id_step, (jnp.int32(0), jnp.max(ids)))
    return hi_mask | (ties & (ids <= jnp.where(n_eq > k, id_thr,
                                               jnp.max(ids))))


def _field_caps(vocab_sizes, capacity: int) -> dict:
    """Per-field hot-tier capacity: ``min(capacity, vocab_f)``."""
    if capacity < 1:
        raise ValueError(f"hot capacity must be >= 1, got {capacity}")
    return {f"field_{i}": min(capacity, v)
            for i, v in enumerate(vocab_sizes)}


def resident_ids(state) -> dict:
    """Per-field int32 arrays of currently hot ids (sentinel-free).
    A slot is occupied iff its id indexes a real table row."""
    out = {}
    for f, sid in state["hot"]["slot_ids"].items():
        s = np.asarray(sid)
        out[f] = s[s < state["hot"]["slot_of"][f].shape[0]]
    return out


_RESIDENCY_MAP_KEYS = ("slot_of", "freq")


def hot_tier_bytes(state) -> int:
    """Bytes of the O(capacity) device-resident working set: hot rows
    (w, m, v, ls) plus the per-slot id map. The O(vocab) residency/
    frequency maps are *not* counted here — they scale with vocab, not
    with the working set, and the async migration path keeps them on the
    host entirely; ``residency_map_bytes`` reports them separately. The
    cold tables (params["embed"], state m/v/last_step) are the
    host-memory tier and excluded from both."""
    total = 0
    for k, sub in state["hot"].items():
        if k in _RESIDENCY_MAP_KEYS:
            continue
        for leaf in jax.tree.leaves(sub):
            total += leaf.size * leaf.dtype.itemsize
    return total


def residency_map_bytes(state) -> int:
    """Bytes of the O(vocab) residency/frequency maps (``slot_of``,
    ``freq``). Device-resident in the synchronous step, host-resident in
    the async migration path — either way they are bookkeeping that grows
    with vocab, so benchmarks report them apart from the hot tier."""
    total = 0
    for k in _RESIDENCY_MAP_KEYS:
        for leaf in jax.tree.leaves(state["hot"].get(k, {})):
            total += leaf.size * leaf.dtype.itemsize
    return total


def make_hotcold_train_step(cfg: ctr.CTRConfig, hp, *, capacity: int = 4096,
                            r: float = 1.0, zeta: float = 1e-5,
                            dense_tx=None, clip: bool = True, b1: float = 0.9,
                            b2: float = 0.999, eps: float = 1e-8,
                            admission: str = "cumulative",
                            half_life: int = 0):
    """Build the hotcold placement's ``(step, init, flush)``.

    Per step, each field's batch ids are deduplicated once
    (``ctr.unique_batch``); every unique row is assembled from the hot
    tier (residency hit) or the cold table (miss), caught up through
    ``t - 1`` in closed form, and updated with the exact sparse reference
    op order (CowClip -> coupled-L2 Adam). The hot set is then re-ranked
    by cumulative frequency over {untouched residents} ∪ {touched ids}
    and rebuilt with one gather from the candidate bank (raw resident
    rows + the just-updated touched rows); every candidate that did not
    make the cut — evicted residents and unadmitted misses — is written
    back raw (w, m, v, last_step) to the cold tables. ``flush``
    reconciles both tiers and settles all pending decay (idempotent);
    residency and frequencies survive a flush.
    """
    from ..train import metrics

    if dense_tx is None:
        dense_tx = optim_lib.adam(hp.dense_lr, l2=hp.dense_l2)
    adam_kw = dict(lr=hp.emb_lr, l2=hp.emb_l2, b1=b1, b2=b2, eps=eps)
    caps = _field_caps(cfg.vocab_sizes, capacity)
    vocab_of = {f"field_{i}": v for i, v in enumerate(cfg.vocab_sizes)}
    alpha = admission_alpha(admission, half_life)

    def init(params):
        embed = params["embed"]
        hot = {
            "w": {g: {f: jnp.zeros((caps[f], t.shape[1]), t.dtype)
                      for f, t in tables.items()}
                  for g, tables in embed.items()},
            "m": {g: {f: jnp.zeros((caps[f], t.shape[1]), jnp.float32)
                      for f, t in tables.items()}
                  for g, tables in embed.items()},
            "v": {g: {f: jnp.zeros((caps[f], t.shape[1]), jnp.float32)
                      for f, t in tables.items()}
                  for g, tables in embed.items()},
            "ls": {g: {f: jnp.zeros((caps[f],), jnp.int32)
                       for f in tables}
                   for g, tables in embed.items()},
            # slot_ids: the id resident in each slot (vocab = empty slot,
            # out of range so every scatter through it drops); slot_of:
            # id -> slot (-1 = cold); freq: cumulative batch counts
            "slot_ids": {f: jnp.full((caps[f],), vocab_of[f], jnp.int32)
                         for f in vocab_of},
            "slot_of": {f: jnp.full((vocab_of[f],), -1, jnp.int32)
                        for f in vocab_of},
            "freq": {f: jnp.zeros((vocab_of[f],), jnp.float32)
                     for f in vocab_of},
        }
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, embed),
            "v": jax.tree.map(jnp.zeros_like, embed),
            "last_step": jax.tree.map(
                lambda t: jnp.zeros((t.shape[0],), jnp.int32), embed),
            "hot": hot,
            "dense": dense_tx.init(params["dense"]),
        }

    def loss_fn(rows, dense_params, uniq, dense_feats, labels):
        logits = ctr.apply_rows(rows, dense_params, cfg, uniq, dense_feats)
        return metrics.logloss(logits, labels)

    def step_impl(params, state, batch):
        t = state["step"] + 1
        uniq = ctr.unique_batch(cfg, batch["ids"])
        hot = state["hot"]
        groups = list(params["embed"].keys())

        # --- residency: which unique slots hit the hot tier
        res = {}
        for f, u in uniq.items():
            V = vocab_of[f]
            uid_c = jnp.minimum(u.uids, V - 1)
            slot = hot["slot_of"][f][uid_c]
            touched = u.counts > 0
            hit = touched & (slot >= 0)
            res[f] = (uid_c, touched, hit, jnp.maximum(slot, 0))

        # --- assemble each unique row from its tier + closed-form catch-up
        # through t-1 (exactly sparse_gather_catchup_reference on the
        # virtual table the two tiers jointly represent)
        w_rows, m_rows, v_rows = ({g: {} for g in groups} for _ in range(3))
        depth = jnp.zeros((), jnp.int32)
        with jax.named_scope("hotcold_assemble_catchup"):
            for f, u in uniq.items():
                uid_c, touched, hit, src = res[f]
                # hits read the hot tier; point their cold-tier lookup at
                # row 0 so the masked gather stays cache-resident instead
                # of touching random rows of the full table
                uid_cold = jnp.where(hit, 0, uid_c)
                h2 = hit[:, None]
                for g in groups:
                    w = jnp.where(h2, hot["w"][g][f][src],
                                  params["embed"][g][f][uid_cold])
                    m = jnp.where(h2, hot["m"][g][f][src],
                                  state["m"][g][f][uid_cold])
                    v = jnp.where(h2, hot["v"][g][f][src],
                                  state["v"][g][f][uid_cold])
                    ls = jnp.where(hit, hot["ls"][g][f][src],
                                   state["last_step"][g][f][uid_cold])
                    depth = jnp.maximum(depth, jnp.max(
                        jnp.where(touched, (t - 1) - ls, 0)))
                    (w_rows[g][f], m_rows[g][f],
                     v_rows[g][f]) = optim_lib.decay_catchup_rows(
                        w.astype(jnp.float32), m, v, ls, t - 1, **adam_kw)

        loss, (g_rows, g_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(
            w_rows, params["dense"], uniq, batch["dense"], batch["labels"])

        # --- row update (reference op order: CowClip -> coupled-L2 Adam),
        # then scatter hits back into the hot tier
        new_embed = {g: dict(params["embed"][g]) for g in groups}
        new_m = {g: dict(state["m"][g]) for g in groups}
        new_v = {g: dict(state["v"][g]) for g in groups}
        new_ls = {g: dict(state["last_step"][g]) for g in groups}
        new_hot = {k: {g: dict(hot[k][g]) for g in groups}
                   for k in ("w", "m", "v", "ls")}
        new_slot_ids, new_slot_of, new_freq = {}, {}, {}
        hits_w = jnp.zeros((), jnp.float32)
        total_w = jnp.zeros((), jnp.float32)
        evictions = jnp.zeros((), jnp.int32)

        for f, u in uniq.items():
            V, C = vocab_of[f], caps[f]
            uid_c, touched, hit, src = res[f]

            # frequency is residency- and capacity-independent under both
            # policies: cumulative just accumulates, decayed halves every
            # half_life steps before adding — either way it depends only
            # on the batches seen (pad uids == V drop)
            fbase = hot["freq"][f]
            if alpha is not None:
                fbase = fbase * alpha
            freq2 = fbase.at[u.uids].add(u.counts, mode="drop")
            new_freq[f] = freq2
            hits_w = hits_w + jnp.sum(jnp.where(hit, u.counts, 0.0))
            total_w = total_w + jnp.sum(u.counts)

            # re-rank: candidates are the current residents plus every
            # touched unique id; top-C by (freq desc, id asc) — the
            # global total order that makes the hot set capacity-monotone.
            # A touched id's up-to-date row sits in the fresh (rows)
            # section of the bank below, so a touched *resident*'s slot
            # entry is masked out (its stale copy must not compete) —
            # which also lets the updated hot tier be one bank gather,
            # with no per-array hit scatters
            tslot = jnp.zeros((C,), bool).at[
                jnp.where(hit, src, C)].set(True, mode="drop")
            res_cand = jnp.where(tslot, V, hot["slot_ids"][f])
            fresh_ids = jnp.where(touched, u.uids, V)
            cand = jnp.concatenate([res_cand, fresh_ids])
            valid = cand < V
            prio = jnp.where(valid, freq2[jnp.minimum(cand, V - 1)], 0.0)
            kept = _top_c_mask(
                jax.lax.bitcast_convert_type(prio, jnp.int32), cand, valid, C)
            # compact the mask into slot order: slot j holds the j-th kept
            # candidate (slot order is arbitrary — slot_of is the map)
            sel = jnp.nonzero(kept, size=C, fill_value=cand.shape[0])[0]
            sel_c = jnp.minimum(sel, cand.shape[0] - 1)
            slot_ids2 = jnp.where(sel < cand.shape[0], cand[sel_c], V)
            wb = valid & ~kept                # evicted or never admitted
            # at most one write-back per admission, and admissions come
            # only from this batch's missed uniques — so compacting wb to
            # the unique capacity keeps every cold scatter O(batch) rows
            # (XLA CPU scatter pays per update row; the uncompacted mask
            # would stream all C + U candidates through 8 table scatters)
            n_wb = u.uids.shape[0]
            wb_idx = jnp.nonzero(wb, size=n_wb, fill_value=cand.shape[0])[0]
            wb_idx_c = jnp.minimum(wb_idx, cand.shape[0] - 1)
            wb_loc = jnp.where(wb_idx < cand.shape[0], cand[wb_idx_c], V)
            # evicted residents: untouched ones fall out of the slot
            # section, touched ones out of the fresh section
            evictions = evictions + jnp.sum(wb[:C].astype(jnp.int32))
            evictions = evictions + jnp.sum((wb[C:] & hit).astype(jnp.int32))

            so = hot["slot_of"][f]
            so = so.at[hot["slot_ids"][f]].set(-1, mode="drop")
            so = so.at[slot_ids2].set(
                jnp.arange(C, dtype=so.dtype), mode="drop")
            new_slot_ids[f], new_slot_of[f] = slot_ids2, so

            for g in groups:
                w_r = w_rows[g][f]
                g32 = g_rows[g][f].astype(jnp.float32)
                if clip:
                    g32 = cowclip_rows(g32, w_r, u.counts, r=r, zeta=zeta)
                w_n, m_n, v_n = optim_lib.sparse_adam_rows(
                    g32, w_r, m_rows[g][f], v_rows[g][f], t, **adam_kw)

                # the candidate bank, aligned with ``cand``: raw resident
                # rows first (touched residents' stale copies masked out
                # of ``cand`` above), every touched row — freshly updated,
                # whichever tier it came from — second
                hw = new_hot["w"][g][f]
                bank_w = jnp.concatenate([hw, w_n.astype(hw.dtype)])
                bank_m = jnp.concatenate([new_hot["m"][g][f], m_n])
                bank_v = jnp.concatenate([new_hot["v"][g][f], v_n])
                bank_ls = jnp.concatenate(
                    [new_hot["ls"][g][f],
                     jnp.full((u.uids.shape[0],), t, jnp.int32)])

                # empty slots (sel == n, slot_ids2 == V) gather a clamped
                # garbage row — never read: assembly and flush both route
                # through the id sentinels
                new_hot["w"][g][f] = bank_w[sel_c]
                new_hot["m"][g][f] = bank_m[sel_c]
                new_hot["v"][g][f] = bank_v[sel_c]
                new_hot["ls"][g][f] = bank_ls[sel_c]

                # eviction = write back the raw row + last_step; pending
                # decay replays in closed form on next touch or at flush
                tbl = new_embed[g][f]
                new_embed[g][f] = tbl.at[wb_loc].set(
                    bank_w[wb_idx_c].astype(tbl.dtype), mode="drop")
                new_m[g][f] = new_m[g][f].at[wb_loc].set(
                    bank_m[wb_idx_c], mode="drop")
                new_v[g][f] = new_v[g][f].at[wb_loc].set(
                    bank_v[wb_idx_c], mode="drop")
                new_ls[g][f] = new_ls[g][f].at[wb_loc].set(
                    bank_ls[wb_idx_c], mode="drop")

        d_updates, d_state = dense_tx.update(
            g_dense, state["dense"], params["dense"])
        new_dense = jax.tree.map(
            lambda p, u_: p + u_.astype(p.dtype), params["dense"], d_updates)
        new_state = {
            "step": t, "m": new_m, "v": new_v, "last_step": new_ls,
            "hot": {"w": new_hot["w"], "m": new_hot["m"], "v": new_hot["v"],
                    "ls": new_hot["ls"], "slot_ids": new_slot_ids,
                    "slot_of": new_slot_of, "freq": new_freq},
            "dense": d_state,
        }
        aux = {"loss": loss, "catchup_depth_max": depth.astype(jnp.int32),
               "hot_hit_rows": hits_w, "hot_lookup_rows": total_w,
               "evictions": evictions}
        return {"embed": new_embed, "dense": new_dense}, new_state, aux

    @jax.jit
    def flush(params, state):
        """Reconcile tiers + settle all pending decay. Scatter every
        resident row home, catch the full tables up through ``step``, and
        re-gather the hot tier from the settled tables — residency,
        frequencies, and slot maps survive. Bit-exactly idempotent: a
        second flush scatters the values it just gathered and replays
        zero decay steps."""
        hot = state["hot"]
        step = state["step"]
        embed = {g: dict(tables) for g, tables in params["embed"].items()}
        m = {g: dict(tb) for g, tb in state["m"].items()}
        v = {g: dict(tb) for g, tb in state["v"].items()}
        ls = {g: dict(tb) for g, tb in state["last_step"].items()}
        for g in embed:
            for f in embed[g]:
                sid = hot["slot_ids"][f]
                embed[g][f] = embed[g][f].at[sid].set(
                    hot["w"][g][f].astype(embed[g][f].dtype), mode="drop")
                m[g][f] = m[g][f].at[sid].set(hot["m"][g][f], mode="drop")
                v[g][f] = v[g][f].at[sid].set(hot["v"][g][f], mode="drop")
                ls[g][f] = ls[g][f].at[sid].set(
                    hot["ls"][g][f], mode="drop")

        caught = jax.tree.map(
            lambda w_, m_, v_, l_: optim_lib.decay_catchup_rows(
                w_, m_, v_, l_, step, **adam_kw),
            embed, m, v, ls)
        outer = jax.tree.structure(embed)
        inner = jax.tree.structure((0, 0, 0))
        new_embed, new_m, new_v = jax.tree.transpose(outer, inner, caught)
        new_embed = jax.tree.map(
            lambda w_, p: w_.astype(p.dtype), new_embed, params["embed"])
        new_ls = jax.tree.map(lambda l_: jnp.full_like(l_, step), ls)

        new_hot = {k: {g: {} for g in embed} for k in ("w", "m", "v", "ls")}
        for g in embed:
            for f in embed[g]:
                sid_c = jnp.minimum(hot["slot_ids"][f], vocab_of[f] - 1)
                new_hot["w"][g][f] = new_embed[g][f][sid_c]
                new_hot["m"][g][f] = new_m[g][f][sid_c]
                new_hot["v"][g][f] = new_v[g][f][sid_c]
                new_hot["ls"][g][f] = jnp.full_like(hot["ls"][g][f], step)
        new_state = dict(
            state, m=new_m, v=new_v, last_step=new_ls,
            hot=dict(hot, w=new_hot["w"], m=new_hot["m"], v=new_hot["v"],
                     ls=new_hot["ls"]))
        return dict(params, embed=new_embed), new_state

    from ..core.builders import jit_step

    return jit_step(step_impl), init, flush


def make_migrate_device_step(cfg: ctr.CTRConfig, hp, *, r: float = 1.0,
                             zeta: float = 1e-5, dense_tx=None,
                             clip: bool = True, b1: float = 0.9,
                             b2: float = 0.999, eps: float = 1e-8):
    """The device half of the async migration split (embed/migrate.py).

    The synchronous step above resolves residency *on device*: it carries
    the O(vocab) ``slot_of``/``freq`` maps and the full cold tables in its
    carry, ranks admission with ``_top_c_mask``, and gathers/scatters the
    cold tier inside the jit — all on the critical path. This step takes
    every one of those decisions as a **fixed-shape input** computed by
    the host-side ``MigrationPlanner`` one step ahead: per field,
    ``hit``/``src``/``ls`` describe the assembly, ``miss_{w,m,v}`` are the
    pre-gathered cold rows, and ``sel``/``wb`` are the bank-gather indices
    for the new hot tier and the eviction output. What remains on device
    is exactly the math whose values matter — assembly select, closed-form
    catch-up, forward/backward, CowClip, coupled-L2 Adam, bank gathers —
    in the *same op order* as the synchronous step, so the two produce
    bitwise-identical rows (tests/test_coldstore.py).

    Signature: ``step(dense_params, dense_opt, hot, t, batch, plan) ->
    (dense_params, dense_opt, hot, evict, aux)`` with ``hot`` =
    ``{"w"|"m"|"v": {group: {field: [C, d]}}}`` (no ls, no maps — those
    are host state now) and ``evict`` the raw ``[U, d]`` eviction banks
    the planner's store-buffer is waiting to fill.
    """
    from ..train import metrics

    if dense_tx is None:
        dense_tx = optim_lib.adam(hp.dense_lr, l2=hp.dense_l2)
    adam_kw = dict(lr=hp.emb_lr, l2=hp.emb_l2, b1=b1, b2=b2, eps=eps)

    def loss_fn(rows, dense_params, uniq, dense_feats, labels):
        logits = ctr.apply_rows(rows, dense_params, cfg, uniq, dense_feats)
        return metrics.logloss(logits, labels)

    def step_impl(dense_params, dense_opt, hot, t, batch, plan):
        # the on-device dedup is O(batch) and must agree with the
        # planner's host replica (np.unique and embedding.sort_unique both
        # emit np.unique's sorted-ascending uids, padded with vocab)
        uniq = ctr.unique_batch(cfg, batch["ids"])
        groups = list(hot["w"].keys())

        w_rows, m_rows, v_rows = ({g: {} for g in groups} for _ in range(3))
        with jax.named_scope("migrate_assemble_catchup"):
            for f, u in uniq.items():
                hit = plan["hit"][f]
                src = plan["src"][f]
                ls = plan["ls"][f]
                h2 = hit[:, None]
                for g in groups:
                    w = jnp.where(h2, hot["w"][g][f][src],
                                  plan["miss_w"][g][f])
                    m = jnp.where(h2, hot["m"][g][f][src],
                                  plan["miss_m"][g][f])
                    v = jnp.where(h2, hot["v"][g][f][src],
                                  plan["miss_v"][g][f])
                    (w_rows[g][f], m_rows[g][f],
                     v_rows[g][f]) = optim_lib.decay_catchup_rows(
                        w.astype(jnp.float32), m, v, ls, t - 1, **adam_kw)

        loss, (g_rows, g_dense) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(
            w_rows, dense_params, uniq, batch["dense"], batch["labels"])

        new_hot = {k: {g: {} for g in groups} for k in ("w", "m", "v")}
        evict = {k: {g: {} for g in groups} for k in ("w", "m", "v")}
        for f, u in uniq.items():
            sel_c = plan["sel"][f]
            wb_c = plan["wb"][f]
            for g in groups:
                w_r = w_rows[g][f]
                g32 = g_rows[g][f].astype(jnp.float32)
                if clip:
                    g32 = cowclip_rows(g32, w_r, u.counts, r=r, zeta=zeta)
                w_n, m_n, v_n = optim_lib.sparse_adam_rows(
                    g32, w_r, m_rows[g][f], v_rows[g][f], t, **adam_kw)

                # same candidate bank as the synchronous step: raw
                # resident rows first, freshly updated touched rows second
                hw = hot["w"][g][f]
                bank_w = jnp.concatenate([hw, w_n.astype(hw.dtype)])
                bank_m = jnp.concatenate([hot["m"][g][f], m_n])
                bank_v = jnp.concatenate([hot["v"][g][f], v_n])
                new_hot["w"][g][f] = bank_w[sel_c]
                new_hot["m"][g][f] = bank_m[sel_c]
                new_hot["v"][g][f] = bank_v[sel_c]
                evict["w"][g][f] = bank_w[wb_c]
                evict["m"][g][f] = bank_m[wb_c]
                evict["v"][g][f] = bank_v[wb_c]

        d_updates, d_state = dense_tx.update(g_dense, dense_opt,
                                             dense_params)
        new_dense = jax.tree.map(
            lambda p, u_: p + u_.astype(p.dtype), dense_params, d_updates)
        return new_dense, d_state, new_hot, evict, {"loss": loss}

    return jax.jit(step_impl, donate_argnums=(0, 1, 2))
