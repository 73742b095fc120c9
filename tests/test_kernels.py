"""Pallas kernel validation: interpret-mode execution swept over shapes and
dtypes, asserted allclose against the pure-jnp oracle (ref.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (coded_decode, coded_decode_apply, coded_encode,
                           coded_encode_acc, ref)

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,V,m", [(1, 8, 1), (3, 64, 2), (5, 640, 4),
                                   (8, 1024, 8), (31, 96, 3)])
def test_encode_2d_sweep(d, V, m, dtype):
    G = jnp.asarray(RNG.standard_normal((d, m, V)), dtype)
    C = jnp.asarray(RNG.standard_normal((d, m)), dtype)
    got = coded_encode(G, C, interpret=True)
    want = ref.coded_encode_ref(G, C)
    assert got.shape == (V,) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,V,m,R", [(3, 16, 2, 128), (4, 256, 2, 64),
                                     (2, 40, 5, 96)])
def test_encode_3d_sweep(d, V, m, R, dtype):
    G = jnp.asarray(RNG.standard_normal((d, m, V, R)), dtype)
    C = jnp.asarray(RNG.standard_normal((d, m)), dtype)
    got = coded_encode(G, C, interpret=True)
    want = ref.coded_encode_ref(G, C)
    assert got.shape == (V, R)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,V,m", [(4, 64, 2), (16, 512, 3), (32, 96, 8),
                                   (10, 1280, 1)])
def test_decode_2d_sweep(n, V, m, dtype):
    F = jnp.asarray(RNG.standard_normal((n, V)), dtype)
    W = jnp.asarray(RNG.standard_normal((n, m)), dtype)
    got = coded_decode(F, W, interpret=True)
    want = ref.coded_decode_ref(F, W)
    assert got.shape == (m, V)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n,V,m,R", [(4, 32, 2, 128), (16, 128, 4, 64)])
def test_decode_3d_sweep(n, V, m, R):
    F = jnp.asarray(RNG.standard_normal((n, V, R)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((n, m)), jnp.float32)
    got = coded_decode(F, W, interpret=True)
    want = ref.coded_decode_ref(F, W)
    assert got.shape == (m, V, R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_kernel_end_to_end_roundtrip():
    """Encode with every worker's coefficients, decode, compare to the plain
    sum of gradients — the kernels reproduce the paper's exact-recovery
    property with a straggler."""
    from repro.core import make_code
    code = make_code(8, d=4, s=2, m=2)
    l = 256
    rng = np.random.default_rng(3)
    Gfull = rng.standard_normal((code.n, l)).astype(np.float32)
    V = l // code.m
    F = []
    for i in range(code.n):
        rows = [(i + j) % code.n for j in range(code.d)]
        G = jnp.asarray(Gfull[rows].reshape(code.d, code.m, V))
        C = jnp.asarray(code.C[i], jnp.float32)
        F.append(np.asarray(coded_encode(G, C, interpret=True)))
    F = jnp.asarray(np.stack(F))
    W = jnp.asarray(code.decode_weights([0, 1, 3, 4, 5, 7]), jnp.float32)
    dec = coded_decode(F, W, interpret=True)          # (m, V)
    got = np.asarray(dec).reshape(-1)
    np.testing.assert_allclose(got, Gfull.sum(0), rtol=1e-4, atol=1e-4)


# ragged shapes: an R past one lane block that is no multiple of 128 (the
# 1/8 qwen3 vocabulary), and 2-D lengths that need lane padding
@pytest.mark.parametrize("shape", [(1, 2, 16, 18992), (2, 3, 40, 300),
                                   (3, 2, 1000), (1, 2, 77)])
def test_encode_acc_ragged_blocks(shape):
    G = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    C = jnp.asarray(RNG.standard_normal(shape[:2]), jnp.float32)
    acc = jnp.asarray(RNG.standard_normal(shape[2:]), jnp.float32)
    want = acc + coded_encode(G, C, interpret=True)
    got = coded_encode_acc(acc, G, C, interpret=True)
    # the fused fold is bit-identical to acc + encode (shared f32 sequence)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = ref.coded_encode_ref(G, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(acc + oracle),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,m,L", [(4, 2, 600 * 128), (3, 1, 1024),
                                   (8, 4, 384)])
def test_decode_apply_matches_decode_then_update(n, m, L):
    """Fused decode + SGD-momentum: params/momentum equal the unfused
    decode -> update up to where the compiler contracts a multiply-add into
    one FMA (an ulp), and the masked sum(g^2) partials (the ragged last
    block at L = 600*128 carries padding rows) agree with the plain sum."""
    F = jnp.asarray(RNG.standard_normal((n, L)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((n, m)), jnp.float32)
    P = jnp.asarray(RNG.standard_normal((m, L)), jnp.float32)
    MU = jnp.asarray(RNG.standard_normal((m, L)), jnp.float32)
    kw = dict(lr=0.1, momentum=0.9, scale=0.25)
    pn, mun, ss = coded_decode_apply(F, W, P, MU, interpret=True, **kw)
    g = coded_decode(F, W, interpret=True, out_dtype=jnp.float32) * 0.25
    mu = 0.9 * MU + g
    np.testing.assert_allclose(np.asarray(mun), np.asarray(mu),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(P - 0.1 * mu),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(ss), float(jnp.sum(g * g)), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,hd,kind,w", [
    (2, 256, 4, 2, 64, "causal", 0),
    (1, 128, 2, 2, 32, "full", 0),
    (2, 256, 4, 4, 64, "window", 64),
    (1, 192, 4, 1, 128, "causal", 0),   # MQA, non-pow2 S
])
def test_flash_attention_sweep(B, S, H, Hkv, hd, kind, w, dtype):
    from repro.kernels.flash_attn import flash_attention_gqa
    from repro.models import common as cm
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, hd)), dtype)
    got = flash_attention_gqa(q, k, v, H // Hkv, mask_kind=kind, window=w,
                              interpret=True, block_q=64, block_k=64)
    want = cm.online_attention(q, k, v, H // Hkv, mask_kind=kind, window=w,
                               chunk_q=64, chunk_kv=64)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ops_wrapper_modes():
    """The ref backend and the interpreted kernels agree on both
    contractions (the einsum oracle vs the Pallas sequence)."""
    from repro.coding import resolve_backend
    a_bk, b_bk = resolve_backend("ref"), resolve_backend("interpret")
    G = jnp.asarray(RNG.standard_normal((3, 2, 64)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((3, 2)), jnp.float32)
    np.testing.assert_allclose(np.asarray(a_bk.encode(G, C)),
                               np.asarray(b_bk.encode(G, C)),
                               rtol=1e-5, atol=1e-5)
    F = jnp.asarray(RNG.standard_normal((4, 64)), jnp.float32)
    W = jnp.asarray(RNG.standard_normal((4, 2)), jnp.float32)
    np.testing.assert_allclose(np.asarray(a_bk.decode(F, W)),
                               np.asarray(b_bk.decode(F, W)),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- block tiling
@pytest.mark.parametrize("A,B,bpe", [(1024, 2048, 8), (8, 18992, 12),
                                     (600, 128, 48), (5, 7, 4),
                                     (1, 151936, 16)])
def test_block_tiles_aligned_and_within_budget(A, B, bpe):
    """Every tile dim is whole or (16, 128)-aligned — never an unaligned
    divisor — and a block stays inside the per-step VMEM budget."""
    from repro.kernels.coded_encode import BLOCK_BYTES, block_tiles
    ta, tb = block_tiles(A, B, bpe)
    assert ta == A or ta % 16 == 0
    assert tb == B or tb % 128 == 0
    assert 0 < ta <= A and 0 < tb <= B
    assert ta * tb * bpe <= BLOCK_BYTES
