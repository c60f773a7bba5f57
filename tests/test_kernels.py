"""Per-kernel validation. The fused CowClip update (``kernels.cowclip``, the
jnp row update XLA compiles) against the optimizer substrate's own chain;
the chunked WKV6 Pallas kernel against its pure-jnp oracle
(``use_kernel=True``: interpret mode on CPU)."""

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # fall back to deterministic parametrized sweeps
    from hypcompat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Hyperparams, apply_updates, build_optimizer
from repro.core.optim import ScaleByAdamState
from repro.kernels.cowclip import fused_cowclip_adam
from repro.kernels.wkv6 import reference as wkv_ref
from repro.kernels.wkv6 import wkv6


# ---------------------------------------------------------------------------
# cowclip fused update
# ---------------------------------------------------------------------------


def _cowclip_inputs(vocab, dim, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    w = (0.01 * jax.random.normal(ks[0], (vocab, dim))).astype(dtype)
    g = (0.1 * jax.random.normal(ks[1], (vocab, dim))).astype(dtype)
    cnt = jax.random.randint(ks[2], (vocab,), 0, 4).astype(jnp.float32)
    m = (0.01 * jax.random.normal(ks[3], (vocab, dim))).astype(dtype)
    v = (0.001 * jnp.abs(jax.random.normal(ks[4], (vocab, dim)))).astype(dtype)
    return w, g, cnt, m, v


def _substrate_step(w, g, cnt, m, v, step, *, r, zeta, lr, l2):
    """One step of ``build_optimizer``'s embedding chain (CowClip -> lazy
    coupled-L2 Adam) from Adam state ``(m, v)`` after ``step - 1`` steps;
    returns the new (w, m, v)."""
    hp = Hyperparams(emb_lr=lr, emb_l2=l2, dense_lr=lr, dense_l2=0.0,
                     batch_size=1)
    tx = build_optimizer(hp, r=r, zeta=zeta)
    params = {"embed": {"t": w}, "dense": {"b": jnp.zeros((1,))}}
    (clip_state, _), dense_state = tx.init(params)
    adam = ScaleByAdamState(count=jnp.asarray(step - 1, jnp.int32),
                            mu={"t": m}, nu={"t": v})
    grads = {"embed": {"t": g}, "dense": {"b": jnp.zeros((1,))}}
    updates, ((_, adam), _) = tx.update(
        grads, ((clip_state, adam), dense_state), params, counts={"t": cnt})
    return (apply_updates(params, updates)["embed"]["t"], adam.mu["t"],
            adam.nu["t"])


@pytest.mark.parametrize("vocab,dim", [
    (64, 8), (1000, 10), (512, 128), (2048, 256), (777, 48), (8, 4096),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_cowclip_kernel_shape_sweep(vocab, dim, dtype):
    w, g, cnt, m, v = _cowclip_inputs(vocab, dim, dtype, seed=vocab + dim)
    step = jnp.asarray(3, jnp.int32)
    kw = dict(r=1.0, zeta=1e-5, lr=1e-4, l2=1e-5)
    out_f = fused_cowclip_adam(w, g, cnt, m, v, step, **kw)
    out_s = _substrate_step(w, g, cnt, m, v, step, **kw)
    for a, b, name in zip(out_f, out_s, ("w", "m", "v")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
            err_msg=f"{name} vocab={vocab} dim={dim}")


@hypothesis.given(
    step=st.integers(1, 10_000),
    r=st.floats(0.1, 10.0),
    zeta=st.sampled_from([1e-5, 1e-4, 1e-3]),
    seed=st.integers(0, 50),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_cowclip_kernel_hyperparam_property(step, r, zeta, seed):
    w, g, cnt, m, v = _cowclip_inputs(128, 8, jnp.float32, seed=seed)
    s = jnp.asarray(step, jnp.int32)
    kw = dict(r=r, zeta=zeta, lr=1e-3, l2=1e-4)
    out_f = fused_cowclip_adam(w, g, cnt, m, v, s, **kw)
    out_s = _substrate_step(w, g, cnt, m, v, s, **kw)
    for a, b in zip(out_f, out_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# chunked wkv6 scan
# ---------------------------------------------------------------------------


def _wkv_inputs(bh, s, n, seed=0, wlog_std=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    r = jax.random.normal(ks[0], (bh, s, n))
    k = jax.random.normal(ks[1], (bh, s, n))
    v = jax.random.normal(ks[2], (bh, s, n))
    # realistic RWKV-6 decay distribution: w = exp(-exp(wlog))
    wlog = -0.6 + wlog_std * jax.random.normal(ks[3], (bh, s, n))
    w = jnp.exp(-jnp.exp(wlog))
    u = 0.1 * jax.random.normal(ks[4], (bh, n))
    return r, k, v, w, u


@pytest.mark.parametrize("bh,s,n", [
    (2, 32, 16), (4, 64, 32), (1, 128, 64), (8, 48, 8),
])
def test_wkv6_kernel_shape_sweep(bh, s, n):
    inp = _wkv_inputs(bh, s, n, seed=bh * s + n)
    yk, sk = wkv6(*inp, use_kernel=True)
    yr, sr = wkv_ref(*inp)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    assert float(jnp.max(jnp.abs(yk - yr))) / scale < 1e-4
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-3,
                               atol=1e-4)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_wkv6_chunk_invariance(chunk):
    inp = _wkv_inputs(2, 64, 16, seed=7)
    yr, sr = wkv_ref(*inp)
    yk, sk = wkv6(*inp, chunk=chunk, use_kernel=True)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    assert float(jnp.max(jnp.abs(yk - yr))) / scale < 1e-4


def test_wkv6_rejects_ragged_seq():
    inp = _wkv_inputs(1, 40, 8)
    with pytest.raises(ValueError):
        wkv6(*inp, chunk=16, use_kernel=True)


def test_wkv6_matches_model_mixer():
    """The kernel agrees with the rwkv module's time-mix scan end-to-end."""
    from repro.models import rwkv

    d_model, n_heads, bsz, seq = 32, 2, 2, 32
    params = rwkv.init_rwkv6(jax.random.key(0), d_model, n_heads)
    x = 0.5 * jax.random.normal(jax.random.key(1), (bsz, seq, d_model))
    y_scan = rwkv.rwkv6_train(params, x, n_heads=n_heads)

    # reproduce the stream computation, then swap in the kernel
    x_shift = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    r, k, v, g, w = rwkv._streams(
        params, x.reshape(-1, d_model), x_shift.reshape(-1, d_model),
        jnp.float32)
    n = d_model // n_heads
    def heads(t):
        return (t.reshape(bsz, seq, n_heads, n).transpose(0, 2, 1, 3)
                .reshape(bsz * n_heads, seq, n))
    u = jnp.broadcast_to(params["u"].reshape(n_heads, n),
                         (bsz, n_heads, n)).reshape(bsz * n_heads, n)
    yk, _ = wkv6(heads(r), heads(k), heads(v), heads(w), u, use_kernel=True)
    yk = yk.reshape(bsz, n_heads, seq, n).transpose(0, 2, 1, 3)  # [B,S,H,N]
    yk = rwkv._head_norm(params, yk)
    # full-module comparison: apply gate + wo to the kernel output
    yk = yk.reshape(bsz, seq, d_model)
    g = g.reshape(bsz, seq, d_model)
    y_kernel = (yk * jax.nn.silu(g)) @ params["wo"]
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_scan),
                               rtol=2e-3, atol=2e-4)


def test_rwkv_chunked_backend_matches_scan():
    """models/rwkv chunked backend (jnp twin of the kernel) == token scan."""
    from repro.models import rwkv

    params = rwkv.init_rwkv6(jax.random.key(3), 64, 4)
    x = 0.5 * jax.random.normal(jax.random.key(4), (2, 64, 64))
    a = rwkv.rwkv6_train(params, x, n_heads=4, backend="scan")
    b = rwkv.rwkv6_train(params, x, n_heads=4, backend="chunked")
    scale = float(jnp.max(jnp.abs(a))) + 1e-9
    assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_rwkv_chunked_backend_ragged_fallback():
    """Non-multiple-of-chunk sequence lengths silently use the token scan."""
    from repro.models import rwkv

    params = rwkv.init_rwkv6(jax.random.key(5), 32, 2)
    x = jax.random.normal(jax.random.key(6), (1, 23, 32))
    out = rwkv.rwkv6_train(params, x, n_heads=2, backend="chunked")
    assert out.shape == (1, 23, 32)
    assert bool(jnp.isfinite(out).all())
