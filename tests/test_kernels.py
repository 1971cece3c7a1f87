"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle, with
shape sweeps and hypothesis property tests."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import classifier, hv
from repro.core.pipeline import HDCConfig, HDCPipeline
from repro.data import ieeg
from repro.kernels.hdc_encoder.kernel import encoder_pallas
from repro.kernels.hdc_encoder.ref import encoder_ref
from repro.kernels.hdc_encoder.ops import encode_frames_fused
from repro.kernels.hdc_am.kernel import am_search_pallas
from repro.kernels.hdc_am.ref import am_search_ref
from repro.kernels.hdc_am.ops import am_search
from repro.kernels.dense_hdc.kernel import dense_encoder_pallas
from repro.kernels.dense_hdc.ref import dense_encoder_ref
from repro.kernels.dense_hdc.ops import dense_encode_frames_fused
from repro.kernels.lbp.kernel import lbp_pallas
from repro.kernels.lbp.ref import lbp_ref
from repro.kernels.lbp.ops import lbp_codes

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# hdc_encoder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,window,c,segments,seg_len", [
    (1, 1, 32, 4, 8, 128),
    (2, 3, 64, 16, 8, 128),
    (1, 2, 32, 8, 4, 64),
    (2, 1, 64, 64, 8, 128),     # paper-shaped channels
    (1, 1, 32, 4, 16, 128),
])
def test_encoder_kernel_vs_ref_shapes(b, f, window, c, segments, seg_len):
    key = jax.random.PRNGKey(b * 100 + f)
    k1, k2 = jax.random.split(key)
    pos = hv.random_sparse_positions(k1, (b, f, window, c), segments, seg_len)
    elec = hv.random_sparse_positions(k2, (c,), segments, seg_len)
    kw = dict(window=window, segments=segments, seg_len=seg_len,
              temporal_threshold=max(1, window // 8))
    out_k = encoder_pallas(pos, elec, interpret=True, **kw)
    out_r = encoder_ref(pos, elec, **kw)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("thinning,thr_s", [(False, 1), (True, 1), (True, 2)])
def test_encoder_kernel_spatial_modes(thinning, thr_s):
    key = jax.random.PRNGKey(5)
    k1, k2 = jax.random.split(key)
    pos = hv.random_sparse_positions(k1, (1, 2, 64, 16), 8, 128)
    elec = hv.random_sparse_positions(k2, (16,), 8, 128)
    kw = dict(window=64, segments=8, seg_len=128, temporal_threshold=8,
              spatial_thinning=thinning, spatial_threshold=thr_s)
    np.testing.assert_array_equal(
        np.asarray(encoder_pallas(pos, elec, interpret=True, **kw)),
        np.asarray(encoder_ref(pos, elec, **kw)))


@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
@settings(max_examples=8, deadline=None)
def test_encoder_kernel_property(seed, thr):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    pos = hv.random_sparse_positions(k1, (1, 1, 32, 8), 8, 128)
    elec = hv.random_sparse_positions(k2, (8,), 8, 128)
    kw = dict(window=32, segments=8, seg_len=128, temporal_threshold=thr)
    np.testing.assert_array_equal(
        np.asarray(encoder_pallas(pos, elec, interpret=True, **kw)),
        np.asarray(encoder_ref(pos, elec, **kw)))


def test_encode_frames_fused_matches_core_classifier():
    """The fused kernel path must be bit-exact with core.classifier on the
    paper configuration and real (synthetic-patient) codes."""
    cfg = classifier.HDCConfig()
    params = classifier.init_params(jax.random.PRNGKey(42), cfg)
    codes = jnp.asarray(ieeg.make_patient(3, n_seizures=1).records[0].codes[None, :2048])
    fused = encode_frames_fused(params, codes, cfg, use_kernel=True)
    unfused = classifier.encode_frames(params, codes, cfg)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


# ---------------------------------------------------------------------------
# hdc_am
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,c,words", [(1, 2, 32), (7, 2, 32), (300, 4, 32),
                                       (64, 2, 16), (5, 8, 64)])
@pytest.mark.parametrize("mode", ["overlap", "hamming"])
def test_am_kernel_vs_ref(b, c, words, mode):
    key = jax.random.PRNGKey(b + c)
    k1, k2 = jax.random.split(key)
    q = jax.random.bits(k1, (b, words), dtype=jnp.uint32)
    cls = jax.random.bits(k2, (c, words), dtype=jnp.uint32)
    dim = words * 32
    out_k = am_search_pallas(q, cls, mode=mode, dim=dim, interpret=True)
    out_r = am_search_ref(q, cls, mode=mode, dim=dim)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_am_ops_leading_dims():
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    q = jax.random.bits(k1, (3, 5, 32), dtype=jnp.uint32)
    cls = jax.random.bits(k2, (2, 32), dtype=jnp.uint32)
    out = am_search(q, cls, mode="overlap", dim=1024)
    assert out.shape == (3, 5, 2)
    np.testing.assert_array_equal(
        np.asarray(out.reshape(-1, 2)),
        np.asarray(am_search_ref(q.reshape(-1, 32), cls, mode="overlap", dim=1024)))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_am_kernel_score_bounds(seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.bits(k1, (4, 32), dtype=jnp.uint32)
    cls = jax.random.bits(k2, (2, 32), dtype=jnp.uint32)
    s = np.asarray(am_search_pallas(q, cls, mode="overlap", dim=1024, interpret=True))
    qpop = np.asarray(hv.popcount(q))
    assert (s >= 0).all() and (s <= qpop[:, None]).all()


# ---------------------------------------------------------------------------
# dense_hdc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,window,c,dim", [
    (1, 1, 32, 4, 1024), (2, 2, 64, 8, 1024), (1, 1, 32, 16, 512)])
def test_dense_kernel_vs_ref(b, f, window, c, dim):
    key = jax.random.PRNGKey(b * 7 + f)
    k1, k2 = jax.random.split(key)
    item = jax.random.bits(k1, (b, f, window, c, dim // 32), dtype=jnp.uint32)
    elec = jax.random.bits(k2, (c, dim // 32), dtype=jnp.uint32)
    out_k = dense_encoder_pallas(item, elec, window=window, dim=dim, interpret=True)
    out_r = dense_encoder_ref(item, elec, window=window, dim=dim)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_dense_fused_matches_core():
    dcfg = HDCConfig(variant="dense")
    pipe = HDCPipeline.init(jax.random.PRNGKey(7), dcfg)
    codes = jnp.asarray(ieeg.make_patient(5, n_seizures=1).records[0].codes[None, :1024])
    fused = dense_encode_frames_fused(pipe.params, codes, dcfg, use_kernel=True)
    unfused = pipe.encode_frames(codes)   # jnp backend = unfused reference
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


# ---------------------------------------------------------------------------
# lbp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,c,bits", [(1, 100, 4, 6), (3, 257, 8, 6),
                                        (2, 64, 64, 4), (1, 1000, 2, 8)])
def test_lbp_kernel_vs_ref(b, t, c, bits):
    x = jax.random.normal(jax.random.PRNGKey(t), (b, t, c))
    out_k = lbp_pallas(x, bits=bits, interpret=True)
    out_r = lbp_ref(x, bits=bits)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_lbp_matches_numpy_reference():
    """Kernel output must agree with the numpy preprocessing used by the
    synthetic-data generator (channel-major ieeg.lbp_codes_np)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 300, 5)).astype(np.float32)
    out = np.asarray(lbp_codes(jnp.asarray(x), use_kernel=True))
    ref = np.stack([ieeg.lbp_codes_np(x[i].T).T for i in range(2)])
    np.testing.assert_array_equal(out, ref)


def test_lbp_long_stream_chunking():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40006, 3))
    out = lbp_codes(x, use_kernel=True)
    assert out.shape == (1, 40000, 3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(lbp_ref(x)))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_lbp_codes_in_range(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 64, 3))
    out = np.asarray(lbp_pallas(x, bits=6, interpret=True))
    assert out.dtype == np.uint8 and (out < 64).all()


# ---------------------------------------------------------------------------
# hdc_fleet: bit-plane masked temporal bundling (ref + fused kernel)
# ---------------------------------------------------------------------------

def _einsum_slot_counts(words, filled, lengths, window):
    """Dense-mask oracle: the pre-bit-plane formulation (unpack -> f32
    einsum against host-built cycle masks), kept as the reference here."""
    s, t, w = words.shape
    k_max = (t - 1) // window + 1
    j = np.arange(t)
    ordinal = (filled[:, None] + j[None, :]) // window
    valid = j[None, :] < lengths[:, None]
    n_emit = (filled + lengths) // window
    rows = np.arange(k_max)
    frame = ((ordinal[:, None, :] == rows[None, :, None])
             & (rows[None, :, None] < n_emit[:, None, None])
             & valid[:, None, :])
    tail = (ordinal >= n_emit[:, None]) & valid
    masks = np.concatenate([frame, tail[:, None, :]], 1).astype(np.float32)
    bits = ((words[..., None] >> np.arange(32, dtype=np.uint32)) & 1)
    bits = bits.reshape(s, t, w * 32).astype(np.float32)
    return np.einsum("skt,std->skd", masks, bits).astype(np.int32)


@pytest.mark.parametrize("t_pad,window", [(8, 32), (32, 32), (64, 32),
                                          (96, 64), (64, 17)])
def test_fleet_counts_ref_matches_einsum_oracle(t_pad, window):
    from repro.kernels.hdc_fleet.ref import fleet_counts_ref
    rng = np.random.default_rng(t_pad * 100 + window)
    s, w = 7, 4
    words = rng.integers(0, 2**32, (s, t_pad, w), dtype=np.uint32)
    filled = rng.integers(0, window, s).astype(np.int32)
    lengths = rng.integers(0, t_pad + 1, s).astype(np.int32)
    got = np.asarray(fleet_counts_ref(
        jnp.asarray(words), jnp.asarray(filled), jnp.asarray(lengths),
        window=window, dim=w * 32))
    want = _einsum_slot_counts(words, filled, lengths, window)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("patients", ["one", "per_session"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("mode,threshold", [("or", 0), ("thin", 3),
                                            ("majority", 0)])
@pytest.mark.parametrize("t", [32, 64, 128, 256])
def test_fleet_kernel_vs_ref(t, mode, threshold, masked, patients):
    """The fused code-domain kernel (bit-table unpack + one-hot matrix
    product + threshold + masked temporal product, sessions walked in owner
    order) must equal the jnp bit-plane path exactly: every bucket, every
    spatial-bundle mode, masked and unmasked, one patient or one per
    session in unsorted order, out-of-alphabet codes, partial ``filled``
    and ``lengths``."""
    from repro.kernels.hdc_fleet.kernel import fleet_counts_pallas
    from repro.kernels.hdc_fleet.ref import emission_masks, fleet_counts_ref
    rng = np.random.default_rng(t + 7 * masked)
    s, c, w, window, k = 5, 6, 2, 32, 8
    p = 1 if patients == "one" else s
    dim = w * 32
    tables = rng.integers(0, 2**32, (p, c, k, w), dtype=np.uint32)
    owner = (rng.permutation(s) if p == s else np.zeros(s)).astype(np.int32)
    codes = rng.integers(0, k + 4, (s, t, c), dtype=np.uint8)  # some OOB
    filled = jnp.asarray(rng.integers(0, window, s), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, t + 1, s), jnp.int32)
    live = (rng.random((s, c)) > 0.3 if masked
            else np.ones((s, c), bool))
    # gather + spatial bundle in numpy -> per-cycle words for the ref path;
    # an out-of-alphabet code clamps within its channel's rows
    bound = tables[owner[:, None, None], np.arange(c)[None, None, :],
                   np.minimum(codes, k - 1)]               # (s, t, c, w)
    bits = ((bound[..., None] >> np.arange(32, dtype=np.uint32)) & 1)
    bits = bits.reshape(s, t, c, dim) * live[:, None, :, None]
    n = live.sum(axis=1)[:, None, None]                    # live channels
    if mode == "or":
        spat = bits.any(axis=2)
    elif mode == "thin":
        spat = bits.sum(axis=2) >= np.maximum(1, -(-threshold * n // c))
    else:
        spat = bits.sum(axis=2) * 2 > n
    words = hv.np_pack_bits(spat.astype(np.uint8))
    ref = np.asarray(fleet_counts_ref(
        jnp.asarray(words), filled, lengths, window=window, dim=dim))
    tm = emission_masks(filled, lengths, t_pad=t, window=window)
    got = np.asarray(fleet_counts_pallas(
        jnp.asarray(tables), jnp.asarray(owner), jnp.asarray(codes), tm,
        mode=mode, dim=dim, threshold=threshold,
        chan_mask=jnp.asarray(live, jnp.uint8) if masked else None,
        interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_fleet_fused_ops_matches_code_domain_jnp():
    """ops.fleet_counts_fused (codes in, counts out, incl. the 32-padding of
    the cycle axis) must match owner_spatial_codes + fleet_counts for a real
    trained bank and a ragged (non-32-multiple) chunk."""
    from repro.kernels.hdc_fleet import ops as fleet_ops
    from repro.serve import dispatch

    cfg = classifier.HDCConfig(dim=256, segments=8, channels=8, window=32,
                               temporal_threshold=4)
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 64, (2, 4 * 32, 8), np.uint8))
    labels = jnp.asarray([[0, 1, 0, 1], [1, 0, 1, 0]])
    pipes = [HDCPipeline.init(jax.random.PRNGKey(i), cfg).train_one_shot(
        codes, labels) for i in range(2)]
    tables, _ = dispatch.stack_bound_tables(pipes)
    owner = jnp.asarray([0, 1, 1, 0, 1], jnp.int32)
    chunk = jnp.asarray(rng.integers(0, 64, (5, 43, 8), np.uint8))
    filled = jnp.asarray(rng.integers(0, 32, 5), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, 44, 5), jnp.int32)
    got = np.asarray(fleet_ops.fleet_counts_fused(
        tables, owner, chunk, filled, lengths, cfg))
    words = dispatch.owner_spatial_codes(tables, owner, chunk, cfg)
    want = np.asarray(fleet_ops.fleet_counts(words, filled, lengths, cfg))
    np.testing.assert_array_equal(got, want)


@given(st.integers(0, 2**63))
@settings(max_examples=10, deadline=None)
def test_fleet_counts_ref_property(seed):
    from repro.kernels.hdc_fleet.ref import fleet_counts_ref
    rng = np.random.default_rng(seed)
    s, t_pad, w, window = 4, 40, 2, 16
    words = rng.integers(0, 2**32, (s, t_pad, w), dtype=np.uint32)
    filled = rng.integers(0, window, s).astype(np.int32)
    lengths = rng.integers(0, t_pad + 1, s).astype(np.int32)
    got = np.asarray(fleet_counts_ref(
        jnp.asarray(words), jnp.asarray(filled), jnp.asarray(lengths),
        window=window, dim=w * 32))
    want = _einsum_slot_counts(words, filled, lengths, window)
    np.testing.assert_array_equal(got, want)
