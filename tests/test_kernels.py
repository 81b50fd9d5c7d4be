"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py.

Tolerances: fp32 kernels accumulate in fp32 but tile order differs from the
oracle's single contraction, so rtol ~1e-4; bf16 inputs get looser bounds.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # noqa: E402 — skips when hypothesis is missing

from repro.kernels import (csr_to_bsr, decode_attention, flash_attention,
                           matmul, ref, rmsnorm, spmv)

_RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = _RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x).astype(dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [
    (128, 256, 128),      # aligned
    (100, 300, 200),      # unaligned → padding path
    (8, 128, 128),        # minimal tile
    (257, 129, 511),      # prime-ish everything
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(M, K, N, dtype):
    x = _rand((M, K), dtype)
    w = _rand((K, N), dtype)
    got = matmul(x, w)
    want = ref.matmul_ref(x, w)
    rtol, atol = (2e-5, 3e-4) if dtype == jnp.float32 else (2e-2, 2e-1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)


def test_matmul_out_dtype():
    x = _rand((64, 128), jnp.bfloat16)
    w = _rand((128, 64), jnp.bfloat16)
    out = matmul(x, w, out_dtype=jnp.float32)
    assert out.dtype == jnp.float32


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,S,d", [
    (1, 2, 2, 64, 32),    # MHA
    (2, 4, 2, 64, 32),    # GQA group 2
    (1, 8, 1, 128, 64),   # MQA
    (1, 2, 2, 100, 32),   # ragged seq → padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_sweep(B, Hq, Hkv, S, d, dtype):
    q = _rand((B, Hq, S, d), dtype)
    k = _rand((B, Hkv, S, d), dtype)
    v = _rand((B, Hkv, S, d), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    kr = jnp.repeat(k, Hq // Hkv, axis=1)
    vr = jnp.repeat(v, Hq // Hkv, axis=1)
    want = ref.flash_attention_ref(q, kr, vr, causal=True)
    rtol, atol = (3e-5, 3e-5) if dtype == jnp.float32 else (2e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)


def test_flash_noncausal():
    q = _rand((1, 2, 64, 32), jnp.float32)
    k = _rand((1, 2, 64, 32), jnp.float32)
    v = _rand((1, 2, 64, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention (ragged lengths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,S,d", [
    (2, 4, 2, 128, 32),
    (1, 8, 8, 256, 64),
    (3, 4, 1, 96, 32),    # unaligned cache length
])
def test_decode_sweep(B, Hq, Hkv, S, d):
    q = _rand((B, Hq, d), jnp.float32)
    kc = _rand((B, Hkv, S, d), jnp.float32)
    vc = _rand((B, Hkv, S, d), jnp.float32)
    lengths = jnp.asarray(_RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    got = decode_attention(q, kc, vc, lengths, block_s=32)
    want = ref.decode_attention_ref(
        q, jnp.repeat(kc, Hq // Hkv, 1), jnp.repeat(vc, Hq // Hkv, 1),
        lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_decode_matches_prefill_last_token():
    """Cross-validation: decode(q_last) == prefill(full)[:, :, -1]."""
    B, H, S, d = 1, 2, 64, 32
    q = _rand((B, H, S, d), jnp.float32)
    k = _rand((B, H, S, d), jnp.float32)
    v = _rand((B, H, S, d), jnp.float32)
    full = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    lengths = jnp.full((B,), S, jnp.int32)
    dec = decode_attention(q[:, :, -1, :], k, v, lengths, block_s=16)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(full[:, :, -1, :]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 128), (1, 1, 1, 256),
                                   (5, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = _rand(shape, dtype)
    w = _rand(shape[-1:], jnp.float32)
    got = rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


# ---------------------------------------------------------------------------
# spmv
# ---------------------------------------------------------------------------

def _random_csr(M, K, density, seed=0):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((M, K)) < density)
             * rng.normal(size=(M, K))).astype(np.float32)
    indptr = [0]
    indices: list[int] = []
    data: list[float] = []
    for r in range(M):
        nz = np.nonzero(dense[r])[0]
        indices.extend(nz.tolist())
        data.extend(dense[r, nz].tolist())
        indptr.append(len(indices))
    return (dense, np.asarray(indptr), np.asarray(indices),
            np.asarray(data, np.float32))


@pytest.mark.parametrize("M,K,density", [
    (64, 256, 0.25),     # the paper's density
    (64, 256, 0.02),     # very sparse
    (16, 128, 0.9),      # nearly dense
])
def test_spmv_sweep(M, K, density):
    dense, indptr, indices, data = _random_csr(M, K, density)
    vals, cols = csr_to_bsr(indptr, indices, data, (M, K), bm=8, bk=128)
    x = jnp.asarray(_RNG.normal(size=(K,)).astype(np.float32))
    got = spmv(jnp.asarray(vals), jnp.asarray(cols), x)
    np.testing.assert_allclose(np.asarray(got)[:M],
                               dense @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)
    # kernel == oracle
    want = ref.spmv_bsr_ref(jnp.asarray(vals), jnp.asarray(cols), x, M)
    np.testing.assert_allclose(np.asarray(got)[:M], np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=0, max_value=1000))
@settings(max_examples=10, deadline=None)
def test_spmv_property_blocked(nbr, nnz, seed):
    """Property: for any BSR structure, kernel == einsum oracle."""
    rng = np.random.default_rng(seed)
    bm, bk = 8, 128
    nbc = nnz + 1
    vals = rng.normal(size=(nbr, nnz, bm, bk)).astype(np.float32)
    cols = rng.integers(-1, nbc, size=(nbr, nnz)).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(nbc * bk,)).astype(np.float32))
    got = spmv(jnp.asarray(vals), jnp.asarray(cols), x)
    want = ref.spmv_bsr_ref(jnp.asarray(vals), jnp.asarray(cols), x,
                            nbr * bm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decoupled_gather — the explicit access/execute kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,R,D", [(8, 32, 128), (16, 64, 128),
                                   (5, 7, 256)])
def test_decoupled_gather_sweep(N, R, D):
    from repro.kernels.decoupled_gather import (decoupled_gather,
                                                decoupled_gather_ref)
    table = _rand((R, D), jnp.float32)
    idx = jnp.asarray(_RNG.integers(0, R, N), jnp.int32)
    got = decoupled_gather(idx, table, interpret=True)
    want = decoupled_gather_ref(idx, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_decoupled_gather_repeated_indices():
    """Ring-buffer correctness when the same row is fetched back-to-back."""
    from repro.kernels.decoupled_gather import (decoupled_gather,
                                                decoupled_gather_ref)
    table = _rand((16, 128), jnp.float32)
    idx = jnp.asarray([3, 3, 3, 5, 3, 5, 5, 0], jnp.int32)
    got = decoupled_gather(idx, table, interpret=True)
    want = decoupled_gather_ref(idx, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# grouped matmul (the held experts of a stacked expert layer)
# ---------------------------------------------------------------------------

#: (rows, routed experts the row tile is sized for, rows of each group
#: (or a count of groups, each row routed to one of twice as many), repeats
#: in the stack, layer, dtype, gate and up fused, K, N)
_GMM_CASES = {
    "empty-groups": (8, 8, [3, 0, 5, 0], 3, 0, jnp.float32, True, 128, 256),
    "one-group-every-row": (100, 4, [0, 100, 0], 2, 1, jnp.bfloat16, False,
                            128, 256),
    "all-rows-elsewhere": (64, 8, [0, 0, 0, 0], 2, 1, jnp.float32, True,
                           128, 128),
    "groups-straddle-tiles": (96, 16, [17, 40, 1, 33], 4, 3, jnp.float32,
                              False, 128, 256),
    "decode-768": (768, 64, 8, 3, 2, jnp.bfloat16, True, 256, 384),
    "prefill-4096k": (4096 * 2, 8, 4, 2, 0, jnp.bfloat16, True,
                      128, 256),
    "n-tiles": (40, 8, [9, 31], 2, 1, jnp.float32, True, 128, 512),
}
#: weight VMEM of a case, small enough to cut N into tiles
_GMM_VMEM = {"n-tiles": 2 * 2 * 128 * 256 * 4}


@pytest.mark.parametrize("case", list(_GMM_CASES))
def test_grouped_matmul_sweep(case, monkeypatch):
    """The kernel over a stack of repeats x groups, this call's groups at
    ``layer``, against ``jax.lax.ragged_dot`` over the same groups packed
    and a float64 numpy product per group: every group's rows, whatever
    their count (none, all, across row tiles), N whole or in tiles, and
    nothing of another repeat's groups."""
    import jax
    from repro.kernels import ops
    P, experts, sizes, L, layer, dtype, fused, K, N = _GMM_CASES[case]
    if case in _GMM_VMEM:
        monkeypatch.setattr(ops, "_GMM_WEIGHT_VMEM", _GMM_VMEM[case])
    n_tiles = N // ops.grouped_block_cols(K, N, 1 + fused,
                                          jnp.dtype(dtype).itemsize)
    assert (n_tiles > 1) == (case in _GMM_VMEM)
    rng = np.random.default_rng(len(case))
    if isinstance(sizes, int):      # half the rows held elsewhere
        sizes = np.bincount(rng.integers(0, 2 * sizes, P),
                            minlength=2 * sizes)[:sizes]
    sizes = np.asarray(sizes, np.int32)
    G = len(sizes)
    tm = ops.grouped_block_rows(P, experts)
    tiles = ops.group_tiles(jnp.asarray(sizes), tm)
    M = ops.grouped_rows(P, G, tm)
    x = _RNG.normal(size=(P, K)).astype(np.float32)
    ws = [_RNG.normal(size=(L * G, K, N)).astype(np.float32) / np.sqrt(K)
          for _ in range(2 if fused else 1)]
    x, ws = (np.asarray(jnp.asarray(a).astype(dtype), np.float32)
             for a in (x, ws))
    # the rows in the kernel's layout: group e from tile tiles[e]
    first = np.asarray(tiles)[:G] * tm
    ends = np.cumsum(sizes) - sizes
    laid = np.zeros((M, K), np.float32)
    for e in range(G):
        laid[first[e]:first[e] + sizes[e]] = x[ends[e]:ends[e] + sizes[e]]
    got = np.asarray(ops.grouped_matmul(
        jnp.asarray(laid).astype(dtype), jnp.asarray(ws[0]).astype(dtype),
        tiles, jnp.int32(layer), block_rows=tm,
        w_up=jnp.asarray(ws[1]).astype(dtype) if fused else None),
        np.float32)
    assert got.shape == (M, N)

    stack_sizes = np.zeros(L * G, np.int32)
    stack_sizes[layer * G:(layer + 1) * G] = sizes
    ragged = [np.asarray(jax.lax.ragged_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(stack_sizes),
        preferred_element_type=jnp.float32)) for w in ws]

    def swiglu(a):
        return a[0] if len(a) == 1 else a[0] / (1 + np.exp(-a[0])) * a[1]

    ragged = swiglu(ragged)
    rtol, atol = (1e-5, 1e-4) if dtype == jnp.float32 else (2e-2, 5e-2)
    for e in range(G):
        rows = slice(ends[e], ends[e] + sizes[e])
        want = swiglu([x[rows].astype(np.float64) @ w[layer * G + e]
                       for w in ws])
        out = got[first[e]:first[e] + sizes[e]]
        np.testing.assert_allclose(out, want, rtol=rtol, atol=atol)
        np.testing.assert_allclose(out, ragged[rows], rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# absorbed-MLA decode (one pass over the live blocks of a stacked latent cache)
# ---------------------------------------------------------------------------

#: (query heads, repeats in the stack, repeat, length, cache dtype, NaN in
#: every dead position of the layer's cache)
_MLA_CASES = {
    "length-0": (16, 3, 0, 0, jnp.float32, False),
    "length-127": (16, 3, 1, 127, jnp.float32, False),
    "length-128": (16, 3, 1, 128, jnp.float32, False),
    "length-767": (16, 3, 2, 767, jnp.float32, False),
    "length-839-partial-block": (16, 3, 1, 839, jnp.float32, False),
    "one-repeat": (16, 1, 0, 500, jnp.float32, False),
    "heads-128": (128, 3, 1, 767, jnp.float32, False),
    "bf16": (16, 3, 1, 600, jnp.bfloat16, False),
    "nan-dead": (16, 3, 1, 300, jnp.float32, True),
}
#: VMEM of a case, small enough for two tiles of 2 batch rows
_MLA_VMEM = {"length-767": 2 * 2 ** 20, "nan-dead": 2 * 2 ** 20}


@pytest.mark.parametrize("case", list(_MLA_CASES))
def test_mla_decode_sweep(case, monkeypatch):
    """``_mla_decode_absorbed`` with its latent attention on the kernel
    against its XLA path, over one repeat of a stack of Moonlight-wide
    latent caches (r 512, rope 64) of 840 positions, a partial last block:
    equal within float32 rounding, or within the rounding of the queries
    and weights to a bf16 cache's dtype; in one or two batch tiles.  With
    NaN in every position past ``length`` of the layer's cache, the
    kernel's output is finite and the same."""
    import dataclasses

    import jax
    from repro.configs import load_config
    from repro.configs.base import MLAConfig
    from repro.kernels import ops
    from repro.models import attention
    H, R, rep, length, dtype, nan = _MLA_CASES[case]
    if case in _MLA_VMEM:
        monkeypatch.setattr(ops, "_MLA_VMEM", _MLA_VMEM[case])
    B, S, r, rope = 4, 840, 512, 64
    assert (ops.mla_decode_block_rows(B, H, r, rope, 4) < B) == (
        case in _MLA_VMEM)
    cfg = dataclasses.replace(
        load_config("moonlight-16b-a3b"), d_model=64, num_heads=H,
        dtype="float32", mla=MLAConfig(
            q_lora_rank=0, kv_lora_rank=r, qk_nope_head_dim=32,
            qk_rope_head_dim=rope, v_head_dim=32))
    params = attention.mla_init(jax.random.PRNGKey(len(case)), cfg)
    q = (_rand((B, 1, H, 32), jnp.float32), _rand((B, 1, H, rope),
                                                  jnp.float32))
    stack = {"c_kv": _rand((R, B, S, r), dtype),
             "k_pe": _rand((R, B, rope, 896), dtype)}

    def attend(path, stack):
        monkeypatch.setattr(attention, "mla_decode_path", lambda: path)
        return np.asarray(attention.mla_decode_attend(
            params, q, stack, jnp.int32(rep), jnp.int32(length), cfg))

    want, got = attend("xla", stack), attend("pallas", stack)
    tol = (1e-5 if dtype == jnp.float32 else 2e-2) * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    if nan:
        dead = {"c_kv": stack["c_kv"].at[rep, :, length + 1:].set(np.nan),
                "k_pe": stack["k_pe"].at[rep, :, :, length + 1:].set(np.nan)}
        poisoned = attend("pallas", dead)
        assert np.isfinite(poisoned).all()
        np.testing.assert_array_equal(poisoned, got)
