"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle.

Kernels execute their real TPU kernel body in Python on CPU via interpret
mode; tolerances account for f32-accumulation vs oracle differences and
bf16 inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.ssd_scan import ssd_scan as ssd_knl


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


def _qkv(key, b, sq, sk, hq, hkv, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, sk, hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, sk, hkv, hd), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # (b, s, hq, hkv, hd, dtype, block); block None: picked from the shape
    (1, 128, 4, 4, 64, jnp.float32, 64),               # MHA
    (2, 256, 8, 2, 64, jnp.float32, 128),              # GQA 4:1
    (1, 384, 4, 1, 32, jnp.float32, 128),              # MQA, non-pow2 seq
    (1, 200, 4, 2, 64, jnp.float32, 64),               # ragged -> padding
    (2, 128, 4, 4, 128, jnp.bfloat16, 64),             # bf16
    (1, 512, 2, 2, 16, jnp.float32, 256),              # tiny head_dim
    # Qwen3-14B's head layout (g = 5, hd 128) at the served S 512 and at
    # 1024 (steps below the diagonal), batch and heads cut; one small
    # unaligned case
    (1, 512, 10, 2, 128, jnp.bfloat16, None),
    (1, 1024, 5, 1, 128, jnp.bfloat16, None),
    (2, 72, 6, 2, 48, jnp.float32, None),
]


@pytest.mark.parametrize("b,s,hq,hkv,hd,dtype,block", FLASH_CASES)
def test_flash_attention_matches_ref(b, s, hq, hkv, hd, dtype, block):
    q, k, v = _qkv(jax.random.PRNGKey(0), b, s, s, hq, hkv, hd, dtype)
    got = fa_ops.flash_attention(q, k, v, causal=True, block=block,
                                 interpret=True)
    want = fa_ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


DECODE_CASES = [
    # (b, s_cache, hq, hkv, hd, length, dtype, block_k)
    (1, 512, 4, 4, 64, 512, jnp.float32, 128),
    (2, 1024, 8, 2, 64, 700, jnp.float32, 256),     # partial fill
    (1, 2048, 4, 1, 128, 1, jnp.float32, 512),      # single valid pos
    (2, 512, 4, 2, 64, 512, jnp.bfloat16, 128),
    (1, 640, 4, 4, 32, 300, jnp.float32, 128),      # ragged block count
]


@pytest.mark.parametrize("b,s,hq,hkv,hd,length,dtype,bk", DECODE_CASES)
def test_decode_attention_matches_ref(b, s, hq, hkv, hd, length, dtype, bk):
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, s, hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, s, hkv, hd), jnp.float32).astype(dtype)
    scale = hd ** -0.5
    got = da_ops.decode_attention(q, k, v, length, scale=scale,
                                  block_k=bk, interpret=True)
    want = fa_ref.decode_attention_ref(q, k, v, length, scale=scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


SSD_CASES = [
    # (b, L, h, p, g, n, chunk, dtype)
    (1, 256, 2, 64, 1, 64, 64, jnp.float32),
    (2, 128, 4, 32, 2, 16, 32, jnp.float32),      # grouped B/C
    (1, 512, 2, 64, 1, 128, 128, jnp.float32),    # mamba2-780m-like
    (1, 128, 2, 64, 1, 16, 64, jnp.float32),      # jamba-like small state
    (1, 256, 2, 64, 1, 64, 64, jnp.bfloat16),
]


def _ssd_inputs(key, b, l, h, p, g, n, dtype):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (b, l, h), jnp.float32) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    bb = jax.random.normal(ks[3], (b, l, g, n), jnp.float32).astype(dtype)
    cc = jax.random.normal(jax.random.fold_in(key, 9),
                           (b, l, g, n), jnp.float32).astype(dtype)
    return x, dt, a, bb, cc


@pytest.mark.parametrize("b,l,h,p,g,n,chunk,dtype", SSD_CASES)
def test_ssd_kernel_matches_ref(b, l, h, p, g, n, chunk, dtype):
    x, dt, a, bb, cc = _ssd_inputs(jax.random.PRNGKey(2), b, l, h, p, g, n,
                                   dtype)
    y_got, s_got = ssd_ops.ssd(x, dt, a, bb, cc, chunk=chunk,
                               impl="pallas_interpret")
    y_want, s_want = ssd_ref.ssd_ref(x, dt, a, bb, cc, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_initial_state_continuation():
    """Splitting a sequence in half and carrying the state must equal the
    full-sequence scan (prefill -> decode continuity)."""
    b, l, h, p, g, n, chunk = 1, 256, 2, 32, 1, 32, 64
    x, dt, a, bb, cc = _ssd_inputs(jax.random.PRNGKey(3), b, l, h, p, g, n,
                                   jnp.float32)
    y_full, s_full = ssd_ref.ssd_ref(x, dt, a, bb, cc, chunk=chunk)
    half = l // 2
    y1, s1 = ssd_knl.ssd_pallas(x[:, :half], dt[:, :half], a, bb[:, :half],
                                cc[:, :half], chunk=chunk, interpret=True)
    y2, s2 = ssd_knl.ssd_pallas(x[:, half:], dt[:, half:], a, bb[:, half:],
                                cc[:, half:], chunk=chunk,
                                initial_state=s1, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, half:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


def test_ssd_ref_matches_naive_recurrence():
    """Chunked oracle vs the literal per-step recurrence."""
    b, l, h, p, g, n = 1, 64, 2, 16, 1, 16
    x, dt, a, bb, cc = _ssd_inputs(jax.random.PRNGKey(4), b, l, h, p, g, n,
                                   jnp.float32)
    y_ref, s_ref = ssd_ref.ssd_ref(x, dt, a, bb, cc, chunk=16)
    rep = h // g
    bh = jnp.repeat(bb, rep, axis=2)
    ch = jnp.repeat(cc, rep, axis=2)
    s = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        decay = jnp.exp(dt[:, t] * a[None, :])               # [B,H]
        s = s * decay[..., None, None] + \
            dt[:, t][..., None, None] * x[:, t][..., :, None] * \
            bh[:, t][..., None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", s, ch[:, t]))
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_naive),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_gradient_is_the_xla_paths(dtype):
    """The kernel's custom VJP (the reference's, recomputed) against
    jax.grad of the model's XLA attention."""
    from repro.models import layers
    b, s, hq, hkv, hd = 1, 128, 4, 2, 128
    q, k, v = _qkv(jax.random.PRNGKey(6), b, s, s, hq, hkv, hd, dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), (b, s, hq, hd))
    spec = layers.AttentionSpec(n_heads=hq, n_kv_heads=hkv, head_dim=hd)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w)

    got = jax.grad(loss(lambda q, k, v: fa_ops.flash_attention(
        q, k, v, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: layers._sdpa(
        q, k, v, spec, layers.causal_mask(s, s))), argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-4, rtol=1e-4)
    for g, r in zip(got, want):
        assert g.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), **tol)


FLASH_RULE = dict(causal=True, cached=False, cross=False, attn_chunk=0,
                  mesh_devices=1, head_dim=128, seq=512)


@pytest.mark.parametrize("platform,change,serves", [
    ("tpu", {}, True),                                # served lm-forward
    ("tpu", {"seq": 1024}, True),
    ("cpu", {}, False),
    ("tpu", {"causal": False}, False),
    ("tpu", {"cached": True}, False),                 # prefill, decode
    ("tpu", {"cross": True}, False),
    ("tpu", {"attn_chunk": 1024}, False),
    ("tpu", {"mesh_devices": 4}, False),              # sharded mesh
    ("tpu", {"head_dim": 64}, False),
    ("tpu", {"seq": 500}, False),
])
def test_flash_dispatch_rule(platform, change, serves):
    from repro.models import layers
    assert layers.flash_serves(platform, **{**FLASH_RULE, **change}) \
        is serves


@pytest.mark.parametrize("seq", [128, 96])
def test_default_attention_on_cpu_is_the_xla_path(seq):
    """At a shape the kernel would take on a TPU (seq 128) and at one it
    would not, the default attention computes on the CPU exactly what
    the XLA path computes, forward and backward."""
    from repro.models import layers
    spec = layers.AttentionSpec(n_heads=4, n_kv_heads=2, head_dim=128)
    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    d = 256
    params = {n: 0.05 * jax.random.normal(kk, shape) for n, kk, shape in
              zip(("wq", "wk", "wv", "wo"), keys,
                  ((d, 512), (d, 256), (d, 256), (512, d)))}
    x = jax.random.normal(keys[4], (2, seq, d))
    pos = jnp.arange(seq)

    def run(impl):
        def loss(p):
            y, _ = layers.attention(p, x, spec, pos, attn_impl=impl)
            return jnp.sum(y * y)
        return jax.jit(jax.value_and_grad(loss))(params)

    got, want = run("auto"), run("xla")
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_flash_attention_refuses_non_causal():
    """The kernel is causal-only; a non-causal call raises instead of
    silently running another implementation."""
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 128, 128, 2, 2, 64,
                   jnp.float32)
    with pytest.raises(NotImplementedError):
        fa_ops.flash_attention(q, k, v, causal=False, interpret=True)


def _gmm_inputs(key, n_pairs, k, n, e, n_w, dtype):
    """A routed layout (`moe.dropless_layout`) of n_pairs rows, with one
    crowded expert and some empty ones, and its operands."""
    from repro.models import moe
    from repro.kernels.moe_gmm.moe_gmm import TILE
    ks = jax.random.split(key, 3 + n_w)
    top_i = jax.random.randint(ks[0], (n_pairs, 1), 0, e // 2) * 2
    top_i = top_i.at[: n_pairs // 2].set(4)
    _, pair, _, tile_expert, n_tiles = moe.dropless_layout(
        top_i, jnp.ones(top_i.shape), e)
    rows = moe.dropless_rows(n_pairs, e)
    lhs = jnp.where((pair < n_pairs)[:, None], jax.random.normal(
        ks[1], (rows, k)), 0).astype(dtype)
    rhs = tuple((jax.random.normal(kk, (e, k, n)) * k ** -0.5).astype(dtype)
                for kk in ks[3:])
    return lhs, rhs, tile_expert, n_tiles, int(n_tiles[0]) * TILE


@pytest.mark.parametrize("n_w,scaled", [(1, False), (2, False), (2, True)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(n_w, scaled, dtype):
    """The grouped-matmul kernel (interpret mode) computes XLA's
    ragged_dot over the tiles in use; with two weights, SwiGLU's first
    half, times each row's scale where one is given."""
    from repro.kernels.moe_gmm import ops, ref
    lhs, rhs, tg, nt, used = _gmm_inputs(jax.random.PRNGKey(11), 700, 256,
                                         384, 8, n_w, dtype)
    scale = (jax.random.uniform(jax.random.PRNGKey(13), lhs.shape[:1])
             if scaled else None)
    got = ops.gmm(lhs, rhs, tg, nt, scale, True)
    want = ref.gmm_ref(lhs, rhs, tg, nt, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[:used], np.float32),
                               np.asarray(want[:used], np.float32),
                               rtol=tol, atol=tol)


def test_moe_gmm_gradient_is_the_xla_paths():
    from repro.kernels.moe_gmm import ops, ref
    lhs, rhs, tg, nt, used = _gmm_inputs(jax.random.PRNGKey(12), 300, 128,
                                         128, 4, 2, jnp.float32)

    def loss(f):
        return lambda x, w: jnp.sum(f(x, w, tg, nt)[:used] ** 2)

    got = jax.grad(loss(lambda *a: ops.gmm(*a, None, True)), (0, 1))(lhs, rhs)
    want = jax.grad(loss(ref.gmm_ref), (0, 1))(lhs, rhs)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)
