"""Per-kernel validation: interpret-mode Pallas vs. pure-jnp oracles over
shape/dtype sweeps (the CPU-side correctness contract for the TPU kernels)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import gather_index_shapes, pallas_grids

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import _bwd_chunked, flash_attention_pallas
from repro.kernels.grid_tick import grid_tick_bank_pallas, grid_tick_pallas
from repro.kernels.mlstm_chunk import mlstm_chunk_pallas
from repro.kernels.selu_mlp import selu_mlp_pallas
from repro.utils import spans

RNG = np.random.RandomState(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# grid_tick
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,T,P,L",
    [(1, 8, 4, 2), (4, 106, 11, 1), (2, 300, 150, 7), (16, 64, 64, 64)],
)
def test_grid_tick_matches_oracle(B, T, P, L):
    proc_of_leg = RNG.randint(0, P, T)
    link_of_proc = RNG.randint(0, L, P)
    m_tp = np.zeros((T, P), np.float32)
    m_tp[np.arange(T), proc_of_leg] = 1
    m_pl = np.zeros((P, L), np.float32)
    m_pl[np.arange(P), link_of_proc] = 1
    m_tl = m_tp @ m_pl
    active = (RNG.rand(B, T) < 0.5).astype(np.float32)
    remaining = RNG.uniform(0.01, 50, (B, T)).astype(np.float32)
    keep = RNG.uniform(0.8, 1, T).astype(np.float32)
    bg = RNG.uniform(-1, 5, (B, L)).astype(np.float32)
    bw = RNG.uniform(10, 100, L).astype(np.float32)
    args = [jnp.asarray(a) for a in (keep, bw, m_tp, m_pl, m_tl)]
    o_ref = jax.vmap(
        lambda a, r, b: ref.grid_tick(a, r, args[0], b, args[1], *args[2:])
    )(jnp.asarray(active), jnp.asarray(remaining), jnp.asarray(bg))
    o_pal = grid_tick_pallas(
        jnp.asarray(active), jnp.asarray(remaining), args[0], jnp.asarray(bg),
        args[1], *args[2:], interpret=True,
    )
    for r, p in zip(o_ref, o_pal):
        np.testing.assert_allclose(np.asarray(r), np.asarray(p), rtol=1e-5, atol=1e-5)


def test_grid_tick_conserves_bandwidth():
    """Sum of per-link campaign transfer never exceeds bandwidth per tick."""
    T, P, L = 64, 32, 4
    proc_of_leg = RNG.randint(0, P, T)
    link_of_proc = RNG.randint(0, L, P)
    m_tp = np.zeros((T, P), np.float32)
    m_tp[np.arange(T), proc_of_leg] = 1
    m_pl = np.zeros((P, L), np.float32)
    m_pl[np.arange(P), link_of_proc] = 1
    m_tl = m_tp @ m_pl
    active = np.ones((1, T), np.float32)
    remaining = np.full((1, T), 1e9, np.float32)
    keep = np.ones(T, np.float32)
    bg = np.zeros((1, L), np.float32)
    bw = RNG.uniform(10, 100, L).astype(np.float32)
    _, _, link_xfer = grid_tick_pallas(
        *[jnp.asarray(a) for a in (active, remaining, keep, bg, bw, m_tp, m_pl, m_tl)],
        interpret=True,
    )
    assert (np.asarray(link_xfer)[0] <= bw + 1e-3).all()


@pytest.mark.parametrize(
    "S,R,T,P,L",
    [(1, 4, 8, 4, 2), (3, 5, 37, 19, 4), (4, 2, 106, 64, 7)],
)
def test_grid_tick_bank_matches_oracle(S, R, T, P, L):
    """Bank-tiled kernel (per-scenario incidences) vs the double-vmapped
    unbatched oracle."""
    m_tp = np.zeros((S, T, P), np.float32)
    m_pl = np.zeros((S, P, L), np.float32)
    for s in range(S):
        m_tp[s, np.arange(T), RNG.randint(0, P, T)] = 1
        m_pl[s, np.arange(P), RNG.randint(0, L, P)] = 1
    m_tl = np.einsum("stp,spl->stl", m_tp, m_pl)
    active = (RNG.rand(S, R, T) < 0.5).astype(np.float32)
    remaining = RNG.uniform(0.01, 50, (S, R, T)).astype(np.float32)
    keep = RNG.uniform(0.8, 1, (S, T)).astype(np.float32)
    bg = RNG.uniform(-1, 5, (S, R, L)).astype(np.float32)
    bw = RNG.uniform(10, 100, (S, L)).astype(np.float32)
    args = [jnp.asarray(a)
            for a in (active, remaining, keep, bg, bw, m_tp, m_pl, m_tl)]
    inner = jax.vmap(ref.grid_tick, in_axes=(0, 0, None, 0, None, None, None, None))
    o_ref = jax.vmap(inner, in_axes=(0,) * 8)(*args)
    o_pal = grid_tick_bank_pallas(*args, interpret=True)
    for r, p in zip(o_ref, o_pal):
        np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# grid_tick under vmap: the batching rule folds mapped sims into kernel rows
# ---------------------------------------------------------------------------
# per case, the in_axes of the outer vmap and of the inner one (None: no
# inner vmap) over active, remaining, keep, bg, bandwidth and the incidences
_ROW = (0, 0, None, 0) + (None,) * 4
_VMAPS = {
    "keep_mapped": ((0, 0, 0, 0) + (None,) * 4, None),
    "keep_shared": (_ROW, None),
    "leap": ((0, None, 0, 0) + (None,) * 4, None),  # the leap step: remaining unmapped
    "nested": ((0, 0, 0, 0) + (None,) * 4, _ROW),  # per-theta keep, replicas inside
    "incidences_mapped": ((0,) * 8, _ROW),  # the vmap lowering of simulate_bank
}
# (case, mapped shape): B not a multiple of 8 or of 256, nested vmaps, and
# per-scenario incidences (the fallback)
_VMAP_CASES = [
    ("keep_mapped", (300,)),
    ("keep_shared", (13,)),
    ("leap", (37,)),
    ("nested", (3, 5)),
    ("incidences_mapped", (3, 4)),
]


def _vmapped(tick, case):
    outer, inner = _VMAPS[case]
    return jax.vmap(tick if inner is None else jax.vmap(tick, in_axes=inner),
                    in_axes=outer)


def _n_mapped(case):
    """How many of the case's vmaps map each operand."""
    outer, inner = _VMAPS[case]
    return [(o is not None) + (i is not None)
            for o, i in zip(outer, inner or (None,) * 8)]


def _case_args(case, shape, T=106, P=11, L=2):
    """Operands of a random campaign, each with as many leading axes as the
    case's vmaps map it over."""
    n_active, n_rem, n_keep, n_bg, n_camp = _n_mapped(case)[:5]
    lead = shape[:n_camp]
    m_tp = np.zeros(lead + (T, P), np.float32)
    m_pl = np.zeros(lead + (P, L), np.float32)
    for s in np.ndindex(*lead):
        m_tp[s][np.arange(T), RNG.randint(0, P, T)] = 1
        m_pl[s][np.arange(P), RNG.randint(0, L, P)] = 1
    arrays = [
        RNG.rand(*shape[:n_active], T) < 0.5,
        RNG.uniform(0.01, 50, shape[:n_rem] + (T,)),
        RNG.uniform(0.8, 1, shape[:n_keep] + (T,)),
        RNG.uniform(-1, 5, shape[:n_bg] + (L,)),
        RNG.uniform(10, 100, lead + (L,)),
        m_tp, m_pl, m_tp @ m_pl,
    ]
    return [jnp.asarray(a, jnp.float32) for a in arrays]


@pytest.mark.parametrize("case,shape", _VMAP_CASES)
def test_vmapped_grid_tick_matches_row_by_row_calls(case, shape):
    """A vmapped ``ops.grid_tick`` (interpret mode) gives, bit for bit, the
    row-by-row unbatched calls and today's Pallas batching (a grid step per
    mapped sim), and the reference at the oracle test's tolerance (its
    one-hot sums add in another order)."""
    args = _case_args(case, shape)
    tick = functools.partial(ops.grid_tick, backend="pallas_interpret")
    before = spans.snapshot()
    out = jax.jit(_vmapped(tick, case))(*args)
    after = spans.snapshot()
    folded, unfolded = (
        after.get(n, spans.Entry(0, 0.0)).count - before.get(n, spans.Entry(0, 0.0)).count
        for n in (ops.TICK_FOLDED, ops.TICK_UNFOLDED)
    )
    if case == "incidences_mapped":
        assert (folded, unfolded) == (1, 1)  # replicas fold, scenarios do not
    else:
        assert (folded, unfolded) == (len(shape), 0)

    one = jax.jit(tick)
    n_mapped = _n_mapped(case)
    for idx in np.ndindex(*shape):
        row = one(*(a[idx[:n]] for a, n in zip(args, n_mapped)))
        for o, r in zip(out, row):
            np.testing.assert_array_equal(np.asarray(o)[idx], np.asarray(r))
    pallas = functools.partial(grid_tick_pallas, interpret=True)
    today = jax.jit(_vmapped(pallas, case))(*args)
    oracle = _vmapped(ref.grid_tick, case)(*args)
    for o, t, r in zip(out, today, oracle):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(t))
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case,shape", _VMAP_CASES + [("keep_mapped", (1000,))])
def test_vmapped_grid_tick_grid_has_row_blocks(case, shape):
    """The mechanism: the folded call is one kernel whose grid holds
    ``ceil(rows / 256)`` row blocks, not one step per mapped sim; mapped
    incidences make the outer axis the bank kernel's scenario axis."""
    args = _case_args(case, shape)
    tick = functools.partial(ops.grid_tick, backend="pallas_interpret")
    grids = pallas_grids(jax.make_jaxpr(_vmapped(tick, case))(*args).jaxpr)
    if case == "incidences_mapped":
        assert grids == [(shape[0], -(-shape[1] // 256))]
    else:
        assert grids == [(1, -(-int(np.prod(shape)) // 256))]
    # what Pallas's own batching rule gives the same call
    pallas = functools.partial(grid_tick_pallas, interpret=True)
    today = pallas_grids(jax.make_jaxpr(_vmapped(pallas, case))(*args).jaxpr)
    assert today == [tuple(shape) + (1,)]


@pytest.mark.parametrize("T,X", [(1, 3), (37, 5), (58, 19), (106, 11)])
def test_onehot_sum_is_bitwise_across_pads_and_batches(T, X):
    """The same legs summed at any zero-padded width and any batch shape
    give the same bits, within float rounding of a float64 sum."""
    m = np.zeros((T, X), np.float32)
    m[np.arange(T), RNG.randint(0, X, T)] = 1
    v = RNG.uniform(0.0, 3000.0, (4, T)).astype(np.float32)
    want = v.astype(np.float64) @ m.astype(np.float64)
    base = np.asarray(ref.onehot_sum(jnp.asarray(v), jnp.asarray(m)))
    np.testing.assert_allclose(base, want, rtol=1e-6)
    for width in (T + 1, 2 * T + 3, 640):
        vp = np.pad(v, ((0, 0), (0, width - T)))
        mp = np.pad(m, ((0, width - T), (0, 0)))
        padded = np.asarray(ref.onehot_sum(jnp.asarray(vp), jnp.asarray(mp)))
        assert np.array_equal(padded, base), width
        one = np.asarray(ref.onehot_sum(jnp.asarray(vp[2]), jnp.asarray(mp)))
        assert np.array_equal(one, base[2]), width
        stacked = np.asarray(ref.onehot_sum(
            jnp.asarray(np.stack([vp, vp])), jnp.asarray(mp[None, None])
        ))
        assert np.array_equal(stacked[1], base), width


def test_gather_legs_matches_onehot_matmul():
    """Index gathers equal the one-hot matmul exactly on real legs, with
    broadcast batch dims."""
    S, R, T, X = 3, 2, 23, 7
    m = np.zeros((S, T, X), np.float32)
    for s in range(S):
        m[s, np.arange(T), RNG.randint(0, X, T)] = 1
    v = RNG.uniform(0.0, 3000.0, (S, R, X)).astype(np.float32)
    want = np.einsum("stx,srx->srt", m.astype(np.float64), v.astype(np.float64))
    idx = ref.leg_index(jnp.asarray(m))[:, None]  # [S, 1, T]
    got = np.asarray(ref.gather_legs(jnp.asarray(v), idx))
    assert got.shape == (S, R, T)
    assert np.array_equal(got, want.astype(np.float32))


def _incidence(S, T, X, pad):
    """One-hot ``[S, T, X]`` incidences whose last ``pad`` legs are padding
    (all-zero rows)."""
    m = np.zeros((S, T, X), np.float32)
    for s in range(S):
        m[s, np.arange(T - pad), RNG.randint(0, X, T - pad)] = 1
    return m


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("R", [1, 2, 64])
def test_bank_take_legs_matches_onehot_matmul(S, R):
    """The bank scan's lookup through a replica-shared ``[S, T]`` index
    equals the float64 one-hot matmul bit for bit on real legs; a padding
    leg reads entry 0 and is masked by ``active``."""
    T, X, pad = 23, 7, 4
    m = _incidence(S, T, X, pad)
    v = RNG.uniform(0.0, 3000.0, (S, R, X)).astype(np.float32)
    active = np.broadcast_to(m.any(-1)[:, None, :], (S, R, T))
    want = np.einsum("stx,srx->srt", m.astype(np.float64), v.astype(np.float64))
    got = np.asarray(ref.bank_take_legs(jnp.asarray(v), ref.leg_index(jnp.asarray(m))))
    assert got.shape == (S, R, T)
    assert np.array_equal(np.where(active, got, 0.0), want.astype(np.float32))
    assert np.array_equal(got[:, :, T - pad:], np.repeat(v[:, :, :1], pad, axis=2))


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("R", [1, 2, 64])
def test_bank_dep_ok_matches_onehot_matmul(S, R):
    """The dependency lookup ``done[s, r, dep[s, t]]`` (``dep = -1``: no
    dependency, always met) equals the float64 one-hot matmul."""
    T = 23
    dep = RNG.randint(-1, T, (S, T)).astype(np.int32)
    dep[:, :3] = -1
    done = RNG.rand(S, R, T) < 0.5
    onehot = (dep[:, :, None] == np.arange(T)).astype(np.float64)  # [S, T, T]
    via_matmul = np.einsum("stu,sru->srt", onehot, done.astype(np.float64)) > 0.5
    want = np.where(dep[:, None, :] >= 0, via_matmul, True)
    got = np.asarray(ref._bank_dep_ok(jnp.asarray(dep), jnp.asarray(done)))
    assert got.shape == (S, R, T)
    assert np.array_equal(got, want)


def test_bank_window_gathers_share_index_across_replicas():
    """No gather of the leap window scan takes an index that carries the
    replica dimension: every lookup reads a ``[S, T]`` index shared by the
    replica rows, not one broadcast to ``[S, R, T]``."""
    S, R, T, P, L = 1, 64, 23, 5, 3
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    state = (
        sd((S, R), i32), sd((S, R), i32), sd((S, R, T), f32),
        sd((S, R, T), jnp.bool_), sd((S, R, T), jnp.bool_),
        sd((S, R, T), i32), sd((S, R, T), i32), sd((S, R, T), f32),
        sd((S, R, T), f32), sd((S, R, L), f32),
    )
    window = jax.jit(functools.partial(ref.grid_tick_bank_window, leap=True))
    hlo = window.lower(
        state, sd((S, 1, L), f32), sd((S, 1, L), f32), sd((S, T), i32),
        sd((S, T), i32), sd((S, L), i32), sd((S,), i32), sd((S, R, T), f32),
        sd((S, L), f32), sd((S, T, P), f32), sd((S, P, L), f32),
        sd((S, T, L), f32), noise=sd((4, S, R, L), f32),
    ).as_text(dialect="hlo")
    shapes = gather_index_shapes(hlo)
    # one gather per distinct operand: per-process, per-link, dependency
    assert len(shapes) == 3, shapes
    assert all(R not in shape for shape in shapes), shapes


def test_grid_tick_ref_broadcasts_batch_dims():
    """The generalized reference accepts stacked operands directly and agrees
    with its own per-scenario evaluation."""
    S, R, T, P, L = 2, 3, 9, 5, 3
    m_tp = np.zeros((S, T, P), np.float32)
    m_pl = np.zeros((S, P, L), np.float32)
    for s in range(S):
        m_tp[s, np.arange(T), RNG.randint(0, P, T)] = 1
        m_pl[s, np.arange(P), RNG.randint(0, L, P)] = 1
    m_tl = np.einsum("stp,spl->stl", m_tp, m_pl)
    active = (RNG.rand(S, R, T) < 0.6).astype(np.float32)
    remaining = RNG.uniform(0.01, 50, (S, R, T)).astype(np.float32)
    keep = RNG.uniform(0.8, 1, (S, T)).astype(np.float32)
    bg = RNG.uniform(0, 5, (S, R, L)).astype(np.float32)
    bw = RNG.uniform(10, 100, (S, L)).astype(np.float32)
    batched = ref.grid_tick(
        jnp.asarray(active), jnp.asarray(remaining), jnp.asarray(keep[:, None]),
        jnp.asarray(bg), jnp.asarray(bw[:, None]), jnp.asarray(m_tp[:, None]),
        jnp.asarray(m_pl[:, None]), jnp.asarray(m_tl[:, None]),
    )
    for s in range(S):
        for r in range(R):
            one = ref.grid_tick(
                jnp.asarray(active[s, r]), jnp.asarray(remaining[s, r]),
                jnp.asarray(keep[s]), jnp.asarray(bg[s, r]), jnp.asarray(bw[s]),
                jnp.asarray(m_tp[s]), jnp.asarray(m_pl[s]), jnp.asarray(m_tl[s]),
            )
            for a, b in zip(batched, one):
                np.testing.assert_allclose(np.asarray(a)[s, r], np.asarray(b),
                                           rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,D,causal,window",
    [
        (2, 64, 64, 4, 2, 32, True, None),
        (1, 100, 100, 2, 2, 64, True, None),
        (1, 128, 128, 4, 1, 48, True, 32),
        (2, 1, 96, 8, 4, 64, True, None),
        (1, 64, 64, 2, 2, 32, False, None),
        (1, 80, 160, 4, 4, 128, True, None),
    ],
)
def test_flash_attention_matches_oracle(B, Sq, Skv, Hq, Hkv, D, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Sq, Hq, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Skv, Hkv, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Skv, Hkv, D)), dtype)
    off = Skv - Sq
    o_ref = ref.flash_attention(q, k, v, causal=causal, window=window, q_offset=off)
    o_pal = flash_attention_pallas(q, k, v, causal, window, None, off, True, 64, 64)
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32), **_tol(dtype)
    )


def test_flash_attention_grad_matches_autodiff():
    q = jnp.asarray(RNG.standard_normal((1, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 64, 2, 32)), jnp.float32)

    def loss_ref(q, k, v):
        return jnp.sum(ref.flash_attention(q, k, v, causal=True) ** 2)

    def loss_pal(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, True, None, None, 0, True, 64, 64) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,blk",
    [
        (2, 64, 8, 4, 32, 32),
        (1, 100, 4, 1, 64, 64),
        (3, 256, 16, 16, 128, 128),
        (2, 33, 2, 2, 16, 32),
    ],
)
def test_decode_attention_matches_oracle(B, S, Hq, Hkv, D, blk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Hq, D)), dtype)
    kc = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    vc = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    lens = jnp.asarray(RNG.randint(1, S + 1, B).astype(np.int32))
    o_ref = ref.decode_attention(q, kc, vc, lens)
    o_pal = decode_attention_pallas(q, kc, vc, lens, interpret=True, blk_s=blk)
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32), **_tol(dtype)
    )


def test_decode_attention_respects_lengths():
    """Changing cache contents beyond `length` must not change the output."""
    B, S, Hq, Hkv, D = 1, 64, 4, 2, 32
    q = jnp.asarray(RNG.standard_normal((B, Hq, D)), jnp.float32)
    kc = np.asarray(RNG.standard_normal((B, S, Hkv, D)), np.float32)
    vc = np.asarray(RNG.standard_normal((B, S, Hkv, D)), np.float32)
    lens = jnp.asarray([40], jnp.int32)
    out1 = decode_attention_pallas(q, jnp.asarray(kc), jnp.asarray(vc), lens, interpret=True, blk_s=32)
    kc[:, 40:] = 1e3
    vc[:, 40:] = -1e3
    out2 = decode_attention_pallas(q, jnp.asarray(kc), jnp.asarray(vc), lens, interpret=True, blk_s=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# mlstm chunk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,S,H,D,chunk",
    [(1, 32, 2, 16, 16), (2, 64, 2, 32, 16), (1, 96, 1, 64, 32), (1, 128, 4, 32, 128)],
)
def test_mlstm_chunk_matches_oracle(B, S, H, D, chunk):
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    ig = jnp.asarray(0.5 * RNG.standard_normal((B, S, H)), jnp.float32)
    fg = jnp.asarray(RNG.standard_normal((B, S, H)) + 2.0, jnp.float32)
    o_ref = ref.mlstm_chunk(q, k, v, ig, fg)
    o_pal = mlstm_chunk_pallas(q, k, v, ig, fg, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal), rtol=5e-4, atol=5e-4)


def test_mlstm_chunk_invariance_to_chunk_size():
    """The chunked evaluation is mathematically chunk-size independent."""
    B, S, H, D = 1, 64, 2, 32
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    ig = jnp.asarray(0.5 * RNG.standard_normal((B, S, H)), jnp.float32)
    fg = jnp.asarray(RNG.standard_normal((B, S, H)) + 2.0, jnp.float32)
    o16 = mlstm_chunk_pallas(q, k, v, ig, fg, chunk=16, interpret=True)
    o64 = mlstm_chunk_pallas(q, k, v, ig, fg, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o16), np.asarray(o64), rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# selu mlp
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "N,fi,h,depth,fo", [(7, 6, 128, 4, 1), (33, 6, 64, 2, 1), (512, 10, 128, 4, 3)]
)
def test_selu_mlp_matches_oracle(N, fi, h, depth, fo, dtype):
    dims = [fi] + [h] * depth + [fo]
    ws = tuple(
        jnp.asarray(RNG.standard_normal((a, b)) * a ** -0.5, dtype)
        for a, b in zip(dims[:-1], dims[1:])
    )
    bs = tuple(jnp.asarray(RNG.standard_normal(b) * 0.1, dtype) for b in dims[1:])
    x = jnp.asarray(RNG.standard_normal((N, fi)), dtype)
    o_ref = ref.selu_mlp(x, ws, bs)
    o_pal = selu_mlp_pallas(x, ws, bs, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_ref, np.float32), np.asarray(o_pal, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------
def test_ops_dispatch_backends_agree():
    from repro.kernels import ops

    q = jnp.asarray(RNG.standard_normal((1, 32, 2, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 32, 2, 16)), jnp.float32)
    o_x = ops.flash_attention(q, k, v, backend="xla")
    o_p = ops.flash_attention(q, k, v, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_p), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,D,causal,window",
    [
        (1, 64, 64, 4, 2, 32, True, None),
        (2, 100, 100, 2, 2, 64, True, None),
        (1, 96, 96, 4, 1, 48, True, 32),
        (1, 64, 128, 2, 2, 32, True, None),  # decode-ish with offset
    ],
)
def test_flash_bwd_kernels_match_autodiff(B, Sq, Skv, Hq, Hkv, D, causal, window):
    """The Pallas dq/dkv backward kernels against autodiff of the oracle."""
    from repro.kernels.flash_attention import (
        _flash_fwd,
        flash_attention_bwd_pallas,
    )

    q = jnp.asarray(RNG.standard_normal((B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    off = Skv - Sq
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(
            ref.flash_attention(a, b, c, causal=causal, window=window,
                                q_offset=off) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    out, lse = _flash_fwd(
        q, k, v, causal=causal, window=window, scale=None, q_offset=off,
        interpret=True, blk_q=32, blk_k=32,
    )
    grads = flash_attention_bwd_pallas(
        q, k, v, out, lse, 2 * out, causal=causal, window=window,
        q_offset=off, interpret=True, blk_q=32, blk_k=32,
    )
    for a, b in zip(g_ref, grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_custom_vjp_is_fully_pallas():
    """grad through flash_attention_pallas runs the Pallas bwd kernels and
    matches the oracle's autodiff."""
    q = jnp.asarray(RNG.standard_normal((1, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 64, 2, 32)), jnp.float32)
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(ref.flash_attention(a, b, c, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_pal = jax.grad(
        lambda a, b, c: jnp.sum(
            flash_attention_pallas(a, b, c, True, None, None, 0, True, 32, 32) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_gqa_grouped_attention_matches():
    """grouped=True (no KV replication) is numerically identical."""
    from repro.kernels.flash_attention import flash_attention_xla

    q = jnp.asarray(RNG.standard_normal((2, 300, 8, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 300, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 300, 2, 32)), jnp.float32)
    o0 = flash_attention_xla(q, k, v, True, None, None, 0, False)
    o1 = flash_attention_xla(q, k, v, True, None, None, 0, True)
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), rtol=2e-5, atol=2e-5)
    g0 = jax.grad(lambda a: jnp.sum(flash_attention_xla(a, k, v, True, None, None, 0, False) ** 2))(q)
    g1 = jax.grad(lambda a: jnp.sum(flash_attention_xla(a, k, v, True, None, None, 0, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=2e-4, atol=2e-4)
