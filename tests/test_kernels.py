"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle.

Every Pallas kernel body is executed on CPU via interpret=True and must be
allclose to its ref.py oracle across a sweep of (N, L, w, b) shapes,
including non-multiples of the block size (padding paths).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import summarization as S
from repro.kernels import ops, ref
from repro.kernels.batch_euclid import batch_euclid_pallas
from repro.kernels.mindist_batch import mindist_batch_pallas
from repro.kernels.mindist_scan import mindist_pallas
from repro.kernels.sax_summarize import sax_summarize_pallas
from repro.kernels.scan_verify import scan_verify_pallas
from repro.kernels.zorder import zorder_pallas

SWEEP = [
    # (n, L, w, b)
    (17, 32, 4, 2),
    (256, 64, 8, 4),
    (300, 128, 16, 8),
    (1, 256, 16, 8),
    (513, 64, 8, 8),
]


def _data(n, L, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, L))
    return S.znormalize(x)


@pytest.mark.parametrize("n,L,w,b", SWEEP)
def test_sax_summarize_kernel(n, L, w, b):
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    bps = S.breakpoints(b)
    paa_k, codes_k = sax_summarize_pallas(x, bps, segments=w,
                                          block_n=64, interpret=True)
    paa_r, codes_r = ref.sax_summarize_ref(x, bps, w)
    np.testing.assert_allclose(np.asarray(paa_k), np.asarray(paa_r),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(codes_k), np.asarray(codes_r))


@pytest.mark.parametrize("n,L,w,b", SWEEP)
def test_zorder_kernel(n, L, w, b):
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    _, codes = S.summarize(x, cfg)
    k_k = zorder_pallas(codes, w=w, b=b, block_n=128, interpret=True)
    k_r = ref.zorder_ref(codes, w=w, b=b)
    assert np.array_equal(np.asarray(k_k), np.asarray(k_r))


@pytest.mark.parametrize("n,L,w,b", SWEEP)
def test_mindist_kernel(n, L, w, b):
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    paa, codes = S.summarize(x, cfg)
    q_paa = paa[0]
    lower = jnp.nan_to_num(S.region_bounds(b)[0], neginf=-1e30)
    upper = jnp.nan_to_num(S.region_bounds(b)[1], posinf=1e30)
    scale = L / w
    m_k = mindist_pallas(q_paa, codes.astype(jnp.int32), lower, upper,
                         scale=scale, block_n=128, interpret=True)
    m_r = ref.mindist_ref(q_paa, codes, lower, upper, scale)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    # lower-bound property against true distances
    ed = np.asarray(ref.batch_euclid_ref(x[0], x))
    assert np.all(np.asarray(m_k) <= ed + 1e-3)


@pytest.mark.parametrize("n,L,w,b", SWEEP)
@pytest.mark.parametrize("nq", [1, 5])
def test_mindist_batch_kernel(n, L, w, b, nq):
    """Batched scan == batched oracle == row-wise single-query oracle."""
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    paa, codes = S.summarize(x, cfg)
    q_paas = S.paa(_data(nq, L, seed=3), w)
    lower = jnp.nan_to_num(S.region_bounds(b)[0], neginf=-1e30)
    upper = jnp.nan_to_num(S.region_bounds(b)[1], posinf=1e30)
    scale = L / w
    m_k = mindist_batch_pallas(q_paas, codes.astype(jnp.int32), lower,
                               upper, scale=scale, block_n=128,
                               interpret=True)
    m_r = ref.mindist_batch_ref(q_paas, codes, lower, upper, scale)
    assert m_k.shape == (nq, n)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    for qi in range(nq):
        row = ref.mindist_ref(q_paas[qi], codes, lower, upper, scale)
        np.testing.assert_allclose(np.asarray(m_r[qi]), np.asarray(row),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,L,w,b", SWEEP)
def test_unpack_codes_kernel_roundtrip(n, L, w, b):
    """The device-side unpacker inverts the v3 storage packer exactly
    (bit-for-bit), including the b == 8 identity degenerate."""
    from repro.storage.packing import pack_codes, packed_code_width
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    _, codes = S.summarize(x, cfg)
    codes_np = np.asarray(codes, np.uint8)
    packed = pack_codes(codes_np, b)
    assert packed.shape == (n, packed_code_width(w, b))
    out = ref.unpack_codes_ref(jnp.asarray(packed), w=w, b=b)
    assert np.array_equal(np.asarray(out), codes_np)


@pytest.mark.parametrize("n,L,w,b", SWEEP)
@pytest.mark.parametrize("nq", [1, 5])
def test_unpack_mindist_kernel(n, L, w, b, nq):
    """Fused unpack+mindist over packed rows: Pallas (interpret) vs the
    fused oracle, and the fused oracle vs the plain batched oracle on
    the decoded rows — the parity the executor's packed fast path
    rests on."""
    from repro.kernels.unpack_mindist import unpack_mindist_batch_pallas
    from repro.storage.packing import pack_codes
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    _, codes = S.summarize(x, cfg)
    packed = jnp.asarray(pack_codes(np.asarray(codes, np.uint8), b))
    q_paas = S.paa(_data(nq, L, seed=3), w)
    lower = jnp.nan_to_num(S.region_bounds(b)[0], neginf=-1e30)
    upper = jnp.nan_to_num(S.region_bounds(b)[1], posinf=1e30)
    scale = L / w
    m_k = unpack_mindist_batch_pallas(q_paas, packed, lower, upper,
                                      w=w, b=b, scale=scale,
                                      block_n=128, interpret=True)
    m_r = ref.mindist_batch_packed_ref(q_paas, packed, lower, upper,
                                       scale=scale, w=w, b=b)
    assert m_k.shape == (nq, n)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)
    # the unpack is exact, so the fused oracle is BIT-equal to the
    # plain oracle on the decoded rows
    m_u = ref.mindist_batch_ref(q_paas, codes, lower, upper, scale)
    assert np.array_equal(np.asarray(m_r), np.asarray(m_u))


def test_mindist_batch_packed_dispatch_modes_agree():
    """ops.mindist_batch_packed equals ops.mindist_batch on the decoded
    column in every dispatch mode (the Partition-level contract)."""
    from repro.storage.packing import pack_codes
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    x = _data(200, 64)
    paa, codes = S.summarize(x, cfg)
    packed = jnp.asarray(pack_codes(np.asarray(codes, np.uint8), 4))
    q_paas = paa[:4]
    want = ops.mindist_batch(q_paas, codes, cfg, mode="jnp")
    for mode in ("jnp", "interpret"):
        got = ops.mindist_batch_packed(q_paas, packed, cfg, mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_mindist_batch_dispatch_modes_agree():
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    x = _data(200, 64)
    paa, codes = S.summarize(x, cfg)
    q_paas = paa[:4]
    base = None
    for mode in ("jnp", "interpret"):
        md = ops.mindist_batch(q_paas, codes, cfg, mode=mode)
        if base is None:
            base = md
        else:
            np.testing.assert_allclose(np.asarray(base), np.asarray(md),
                                       rtol=1e-5, atol=1e-5)
    # agrees with the core helper used by exact_search_batch
    core = S.mindist_sq_batch(q_paas, codes, cfg)
    np.testing.assert_allclose(np.asarray(base), np.asarray(core),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,L", [(17, 32), (256, 64), (1000, 256), (1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batch_euclid_kernel(n, L, dtype):
    x = _data(n, L).astype(dtype)
    q = x[0]
    e_k = batch_euclid_pallas(q, x, block_n=128, interpret=True)
    e_r = ref.batch_euclid_ref(q, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r),
                               rtol=tol, atol=tol)


def test_ops_dispatch_modes_agree():
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    x = _data(200, 64)
    for mode in ("jnp", "interpret"):
        paa, codes = ops.sax_summarize(x, cfg, mode=mode)
        keys = ops.zorder(codes.astype(jnp.uint8), cfg, mode=mode)
        md = ops.mindist(paa[0], codes, cfg, mode=mode)
        ed = ops.batch_euclid(x[0], x, mode=mode)
        if mode == "jnp":
            base = (paa, codes, keys, md, ed)
        else:
            for a, b in zip(base, (paa, codes, keys, md, ed)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float64), np.asarray(b, np.float64),
                    rtol=1e-5, atol=1e-5)


def test_fused_summarize_and_key():
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    x = _data(100, 64)
    paa, codes, keys = ops.summarize_and_key(x, cfg, mode="interpret")
    keys_want = S.invsax_keys(codes.astype(jnp.uint8), cfg)
    assert np.array_equal(np.asarray(keys), np.asarray(keys_want))


@pytest.mark.parametrize("n,L,w,b", [(100, 64, 8, 4), (257, 256, 16, 8)])
def test_fused_build_kernel(n, L, w, b):
    """Fused raw->keys kernel == the three-op reference pipeline."""
    from repro.kernels.fused_build import fused_build_pallas
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    bps = S.breakpoints(b)
    paa_k, codes_k, keys_k = fused_build_pallas(
        x, bps, segments=w, bits=b, block_n=64, interpret=True)
    paa_r, codes_r = ref.sax_summarize_ref(x, bps, w)
    keys_r = ref.zorder_ref(codes_r, w=w, b=b)
    np.testing.assert_allclose(np.asarray(paa_k), np.asarray(paa_r),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(codes_k), np.asarray(codes_r))
    assert np.array_equal(np.asarray(keys_k), np.asarray(keys_r))


# ------------------------------------------------- fused scan+verify kernel

@pytest.mark.parametrize("n,L,w,b", [(17, 32, 4, 2), (256, 64, 8, 4),
                                     (300, 128, 16, 8), (513, 64, 8, 8)])
@pytest.mark.parametrize("nq,k", [(1, 1), (5, 3)])
def test_scan_verify_kernel(n, L, w, b, nq, k):
    """Fused bound+verify+top-k (interpret mode) vs the jnp oracle:
    identical counts, matching top-k distances, and every returned index
    really has the returned distance."""
    cfg = S.SummaryConfig(series_len=L, segments=w, bits=b)
    x = _data(n, L)
    paa, codes = S.summarize(x, cfg)
    queries = _data(nq, L, seed=3)
    q_paas = S.paa(queries, w)
    lower = jnp.nan_to_num(S.region_bounds(b)[0], neginf=-1e30)
    upper = jnp.nan_to_num(S.region_bounds(b)[1], posinf=1e30)
    scale = L / w
    # a mid-range bound so some rows are pruned and some verified
    ed = np.asarray(ref.batch_euclid_multi_ref(queries, x))
    bound = jnp.asarray(np.median(ed, axis=1).astype(np.float32))
    dead = jnp.zeros(n, jnp.int32).at[: n // 5].set(1)
    d_k, i_k, c_k, u_k = scan_verify_pallas(
        queries, q_paas, codes.astype(jnp.int32), x, lower, upper,
        bound, dead, scale=scale, k=k, block_n=128, interpret=True)
    d_r, i_r, c_r, u_r = ref.scan_verify_ref(
        queries, q_paas, codes, x, lower, upper, bound, dead,
        scale=scale, k=k)
    assert np.array_equal(np.asarray(c_k), np.asarray(c_r))
    assert int(u_k) == int(u_r)
    assert int(u_k) <= int(np.asarray(c_k).sum())
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-5, atol=1e-5)
    ed_np = np.asarray(ed)
    for qi in range(nq):
        for j in range(k):
            idx = int(np.asarray(i_k)[qi, j])
            dv = float(np.asarray(d_k)[qi, j])
            if np.isfinite(dv):
                assert idx >= 0
                np.testing.assert_allclose(ed_np[qi, idx], dv,
                                           rtol=1e-5, atol=1e-5)
            else:
                assert idx == -1


def test_scan_verify_dispatch_modes_agree():
    cfg = S.SummaryConfig(series_len=64, segments=8, bits=4)
    x = _data(200, 64)
    paa, codes = S.summarize(x, cfg)
    queries = _data(4, 64, seed=7)
    q_paas = S.paa(queries, 8)
    bound = jnp.full(4, 1e9, jnp.float32)
    base = None
    for mode in ("jnp", "interpret"):
        d, i, c, u = ops.scan_verify(queries, q_paas, codes, x, bound,
                                     cfg, k=3, mode=mode)
        if base is None:
            base = (d, c, u)
        else:
            np.testing.assert_allclose(np.asarray(base[0]), np.asarray(d),
                                       rtol=1e-5, atol=1e-5)
            assert np.array_equal(np.asarray(base[1]), np.asarray(c))
            assert int(base[2]) == int(u)


def test_batch_euclid_default_resolves_by_backend(monkeypatch):
    """Satellite: batch_euclid_pallas no longer hard-codes
    interpret=True — the default resolves through the backend policy
    (interpret off-TPU), and ops.batch_euclid stays the dispatch home."""
    import inspect
    sig = inspect.signature(batch_euclid_pallas)
    assert sig.parameters["interpret"].default is None
    x = _data(64, 32)
    got = batch_euclid_pallas(x[0], x, block_n=32)    # CPU -> interpret
    want = ref.batch_euclid_ref(x[0], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_scan_verify_query_chunks_carry_eager_bits():
    """Q=11 at the paper's L=256: the kernel verifies in chunks of 8
    queries (the batch padded to 16) and every distance it returns has
    the eager verifier's bits."""
    cfg = S.SummaryConfig(series_len=256, segments=16, bits=8)
    x = _data(600, 256)
    _paa, codes = S.summarize(x, cfg)
    queries = _data(11, 256, seed=9)
    lower = jnp.nan_to_num(S.region_bounds(8)[0], neginf=-1e30)
    upper = jnp.nan_to_num(S.region_bounds(8)[1], posinf=1e30)
    d_k, i_k, c_k, _u = scan_verify_pallas(
        queries, S.paa(queries, 16), codes.astype(jnp.int32), x, lower,
        upper, jnp.full(11, jnp.inf, jnp.float32), jnp.zeros(600, jnp.int32),
        scale=16.0, k=4, block_n=256, interpret=True)
    ed = np.asarray(S.euclidean_sq_batch(queries, x))
    want = np.argsort(ed, axis=1, kind="stable")[:, :4]
    assert np.array_equal(np.asarray(i_k), want)
    assert np.array_equal(np.asarray(d_k),
                          np.take_along_axis(ed, want, axis=1))
    assert np.array_equal(np.asarray(c_k), np.full(11, 600))


def _pairwise_sum_np(sq: np.ndarray) -> np.ndarray:
    """The documented order of adds, in numpy float32: zero-pad the last
    axis to a power of two, then element i meets element i + h as h
    halves down to 1."""
    p = 1 << (sq.shape[-1] - 1).bit_length()
    s = np.zeros(sq.shape[:-1] + (p,), np.float32)
    s[..., :sq.shape[-1]] = sq
    while s.shape[-1] > 1:
        h = s.shape[-1] // 2
        s = s[..., :h] + s[..., h:]
    return s[..., 0]


@pytest.mark.parametrize("L", [32, 100, 256])
def test_distance_bits_fixed_by_order(L):
    """A row's squared distance is the documented pairwise sum, whatever
    the batch or block it is computed in: the same bits alone, in a
    batch of 16, over a row block, and through the ``batch_euclid``
    kernel."""
    x = np.asarray(_data(300, L))
    q = np.asarray(_data(16, L, seed=5))
    diff = x[None, :, :] - q[:, None, :]
    want = _pairwise_sum_np(diff * diff)
    full = np.asarray(S.euclidean_sq_batch(q, x))
    assert np.array_equal(full, want)
    for qi in (0, 7, 15):
        one = np.asarray(S.euclidean_sq_batch(q[qi:qi + 1], x[40:77]))
        assert np.array_equal(one[0], want[qi, 40:77])
        assert np.array_equal(np.asarray(S.euclidean_sq(q[qi], x)),
                              want[qi])
        kern = batch_euclid_pallas(q[qi], x, block_n=128, interpret=True)
        assert np.array_equal(np.asarray(kern), want[qi])
