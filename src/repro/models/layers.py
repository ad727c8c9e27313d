"""Core neural-net layers shared by every architecture in the module zoo.

Pure-functional JAX: params are plain pytrees of arrays; every function takes
(params, inputs, config-ish kwargs) and returns arrays.  Sharding is applied
by the caller via logical-axis annotations (see repro.sharding.partition).

Attention paths:
  - full/teacher-forced:  _sdpa (reference) | _chunked_sdpa (q-block scan,
    avoids materialising S x S scores) | Pallas flash kernel
  - decode (1 token):     local cached attention, or *sequence-sharded* cache
    attention under shard_map with an online-softmax merge across shards
    (production path: works for any kv_heads vs TP degree and spreads the
    KV-cache HBM traffic across the whole mesh axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import partition

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (llama-style half rotation)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, n_heads, head_dim]; positions: [seq] int32."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)             # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = angles[..., None, :]                            # [..., s, 1, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    qk_norm: bool = False
    causal: bool = True
    use_rope: bool = True
    bias: bool = False
    softmax_scale: float | None = None
    attn_chunk: int = 0          # q-block size for chunked attention (0=off)
    attn_unroll: bool = False    # python-unroll the q-block loop

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def scale(self) -> float:
        return self.softmax_scale or self.head_dim ** -0.5


def _project_qkv(params, x, spec: AttentionSpec, positions):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(x.dtype))
    if spec.bias:
        q = q + params["bq"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, spec.n_heads, spec.head_dim)
    k = k.reshape(b, s, spec.n_kv_heads, spec.head_dim)
    v = v.reshape(b, s, spec.n_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _scores_mask(scores, mask):
    return scores if mask is None else jnp.where(mask, scores, -1e30)


def _sdpa(q, k, v, spec: AttentionSpec, mask) -> jax.Array:
    """Reference attention. q:[B,Sq,Hq,hd] k,v:[B,Sk,Hkv,hd].

    GQA KV heads are repeated up to the q-head count so that *all* attention
    intermediates shard evenly by q-head over the TP axis (kv_heads is
    usually < TP degree; sharding by kv-head would pad and replicate the
    big [.., Sq, Sk] score tensor).  The repeat materialises g copies of
    K/V — negligible next to scores — and the Pallas kernel on real TPU
    handles GQA natively without it.
    """
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # "seq_attn" (unsharded) not "seq": inside attention the sequence is
    # gathered and the head axis carries the TP sharding instead
    q = partition.constrain(q, ("batch", "seq_attn", "heads", None))
    k = partition.constrain(k, ("batch", "seq_attn", "heads", None))
    v = partition.constrain(v, ("batch", "seq_attn", "heads", None))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * spec.scale
    if mask is not None:
        mask = mask.reshape(mask.shape[0], mask.shape[1],
                            *mask.shape[-2:])          # [1|B,1,Sq,Sk]
    scores = _scores_mask(scores, mask)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out.reshape(b, sq, hq, hd)


def _chunked_sdpa(q, k, v, spec: AttentionSpec, q_offset, causal=True):
    """Attention evaluated per q-block so the [Sq, Sk] score matrix never
    materialises at once.  q_offset: absolute position of q[0] minus k[0]
    (for causal masking).  Falls back to python unroll when spec.attn_unroll
    (used by dry-run cost compiles so HLO counts every block)."""
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    qc = spec.attn_chunk
    pad = (-sq) % qc
    if pad:  # pad q rows; padded queries attend causally and are sliced off
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = _chunked_sdpa(q, k, v, spec, q_offset, causal)
        return out[:, :sq]
    nb = sq // qc
    kpos = jnp.arange(sk)[None, :]

    def block(qb, start):
        mask = None
        if causal:
            qpos = q_offset + start + jnp.arange(qc)[:, None]
            mask = (kpos <= qpos)[None, None, None]
        return _sdpa(qb, k, v, spec, mask)

    if spec.attn_unroll:
        outs = [block(q[:, i * qc:(i + 1) * qc], i * qc) for i in range(nb)]
        return jnp.concatenate(outs, axis=1)

    qb = q.reshape(b, nb, qc, hq, hd)

    def body(_, xs):
        qi, i = xs
        return None, block(qi, i * qc)

    _, outs = jax.lax.scan(body, None,
                           (jnp.moveaxis(qb, 1, 0), jnp.arange(nb)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, hq, hd)


def _local_cached_attention(q, k_cache, v_cache, spec, cache_pos):
    """Single-device decode/prefill attention over a cache."""
    b, s = q.shape[0], q.shape[1]
    s_max = k_cache.shape[1]
    qi = cache_pos + jnp.arange(s)[:, None]
    ki = jnp.arange(s_max)[None, :]
    valid = (ki <= qi)[None, None, None]
    return _sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                 spec, valid)


def _flat_axes(axes) -> tuple:
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        out = []
        for a in axes:
            out.extend(_flat_axes(a))
        return tuple(out)
    return (axes,)


def _n_seq_shards(mesh, rules) -> int:
    n = 1
    for a in _flat_axes(rules.get("seq_kv")):
        n *= mesh.shape[a]
    return n


def sharded_cache_attention(q, k_cache, v_cache, spec: AttentionSpec,
                            cache_pos, mesh, rules, causal=True):
    """Decode attention over a *sequence-sharded* KV cache.

    q: [B, s, Hq, hd] (replicated over the seq-shard axes); caches
    [B, S, Hkv, hd] sharded over rules["seq_kv"].  Each shard computes
    partial attention over its local cache slice; partials merge with an
    online-softmax (pmax/psum) reduction — numerically identical to global
    softmax.  This works for any (kv_heads, TP) combination and spreads
    cache HBM traffic across the mesh.
    """
    batch_axes = rules.get("batch")
    seq_axes = rules.get("seq_kv")
    seq_flat = _flat_axes(seq_axes)
    if not seq_flat:
        return _local_cached_attention(q, k_cache, v_cache, spec, cache_pos)
    n_shards = 1
    for a in seq_flat:
        n_shards *= mesh.shape[a]
    s_valid = k_cache.shape[1]
    pad = (-s_valid) % n_shards
    if pad:  # masked below via s_valid
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_spec = P(batch_axes, None, None, None)
    kv_spec = P(batch_axes, seq_axes, None, None)

    def body(qb, kb, vb):
        bl, s, hq, hd = qb.shape
        s_loc = kb.shape[1]
        hkv = kb.shape[2]
        g = hq // hkv
        idx = jnp.zeros((), jnp.int32)
        for a in seq_flat:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        start = idx * s_loc
        qg = qb.reshape(bl, s, hkv, g, hd)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(qb.dtype),
                            preferred_element_type=jnp.float32) * spec.scale
        kpos = start + jnp.arange(s_loc)[None, :]
        qpos = (cache_pos + jnp.arange(s))[:, None]
        mask = (kpos <= qpos) if causal else (kpos < s_valid)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        m_loc = jnp.max(scores, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, seq_flat)
        m_glob = jnp.maximum(m_glob, -1e30)  # all-masked guard
        p = jnp.exp(scores - m_glob)
        l_loc = jnp.sum(p, axis=-1)                          # [b,h,g,s]
        o_loc = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vb.dtype),
                           vb, preferred_element_type=jnp.float32)
        l_glob = jax.lax.psum(l_loc, seq_flat)
        o_glob = jax.lax.psum(o_loc, seq_flat)
        # l_glob [b,h,g,s] -> [b,s,h,g,1] to divide o_glob [b,s,h,g,hd]
        out = o_glob / jnp.moveaxis(l_glob, 3, 1)[..., None]
        return out.reshape(bl, s, hq, hd).astype(qb.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False)(q, k_cache, v_cache)


def sharded_cache_update_attention(q, k_new, v_new, k_cache, v_cache,
                                   spec: AttentionSpec, cache_pos, mesh,
                                   rules):
    """Single-token decode with the cache update *inside* the shard_map.

    The cache sequence axis is sharded; a global dynamic_update_slice at a
    traced position makes GSPMD replicate the whole cache (measured: the
    dominant decode HBM term and the reason big-arch decode cells blew the
    16 GiB budget).  Here each shard checks whether `cache_pos` lands in
    its local slice and performs a local, in-place (donated) update; the
    attention merge is the same online-softmax as sharded_cache_attention.

    q: [B, 1, Hq, hd]; k_new/v_new: [B, 1, Hkv, hd]; caches [B, S, Hkv, hd].
    Returns (out [B,1,Hq,hd], k_cache, v_cache).
    """
    batch_axes = rules.get("batch")
    seq_axes = rules.get("seq_kv")
    seq_flat = _flat_axes(seq_axes)
    assert seq_flat, "requires a sequence-sharded cache"
    q_spec = P(batch_axes, None, None, None)
    kv_new_spec = P(batch_axes, None, None, None)
    kv_spec = P(batch_axes, seq_axes, None, None)

    def body(qb, knb, vnb, kb, vb):
        bl, s, hq, hd = qb.shape
        s_loc = kb.shape[1]
        hkv = kb.shape[2]
        g = hq // hkv
        idx = jnp.zeros((), jnp.int32)
        for a in seq_flat:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        start = idx * s_loc
        # ---- shard-local cache update ----
        local = cache_pos - start
        in_range = (local >= 0) & (local < s_loc)
        at = jnp.clip(local, 0, s_loc - 1)

        def upd(cache, new):
            old = jax.lax.dynamic_slice(cache, (0, at, 0, 0),
                                        (bl, 1, hkv, hd))
            piece = jnp.where(in_range, new.astype(cache.dtype), old)
            return jax.lax.dynamic_update_slice(cache, piece, (0, at, 0, 0))

        kb = upd(kb, knb)
        vb = upd(vb, vnb)
        # ---- partial attention + online-softmax merge ----
        qg = qb.reshape(bl, s, hkv, g, hd)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb.astype(qb.dtype),
                            preferred_element_type=jnp.float32) * spec.scale
        kpos = start + jnp.arange(s_loc)[None, :]
        qpos = (cache_pos + jnp.arange(s))[:, None]
        scores = jnp.where((kpos <= qpos)[None, None, None], scores,
                           -jnp.inf)
        m_loc = jnp.max(scores, axis=-1, keepdims=True)
        m_glob = jnp.maximum(jax.lax.pmax(m_loc, seq_flat), -1e30)
        p = jnp.exp(scores - m_glob)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
        l_glob = jax.lax.psum(l_loc, seq_flat)
        o_glob = jax.lax.psum(o_loc, seq_flat)
        out = o_glob / jnp.moveaxis(l_glob, 3, 1)[..., None]
        return out.reshape(bl, s, hq, hd).astype(qb.dtype), kb, vb

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, kv_new_spec, kv_new_spec, kv_spec, kv_spec),
        out_specs=(q_spec, kv_spec, kv_spec), check_vma=False,
    )(q, k_new, v_new, k_cache, v_cache)


def flash_serves(platform: str, *, causal: bool, cached: bool,
                 cross: bool, attn_chunk: int, mesh_devices: int,
                 head_dim: int, seq: int) -> bool:
    """Whether the default attention (`attn_impl="auto"`) runs on the
    Pallas flash kernel, from what the program can observe when it is
    traced and lowered: causal self-attention without a KV cache, not
    chunked, on one device, at a head_dim and sequence length that suit
    the kernel's 128-lane tiling, lowered for a TPU.  Everything else
    takes the XLA path."""
    return (platform == "tpu" and causal and not cached and not cross
            and not attn_chunk and mesh_devices == 1
            and head_dim % 128 == 0 and seq % 128 == 0)


def _mesh_devices(x, mesh) -> int:
    """Devices the traced program spans: the mesh passed in, or the
    abstract mesh of the traced array's sharding (set by `jit` from its
    arguments' shardings)."""
    traced = jax.typeof(x).sharding.mesh
    return max(mesh.size if mesh is not None else 1,
               traced.size if traced.axis_names else 1)


def _self_attention(q, k, v, spec: AttentionSpec, attn_impl: str, mesh):
    """Self-attention without a cache over fresh q, k, v."""
    s = q.shape[1]

    def flash(q, k, v, interpret=False):
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=True, scale=spec.scale,
                                      interpret=interpret)

    def xla(q, k, v):
        if spec.attn_chunk and s > spec.attn_chunk:
            return _chunked_sdpa(q, k, v, spec, 0, causal=spec.causal)
        mask = causal_mask(s, s) if spec.causal else None
        return _sdpa(q, k, v, spec, mask)

    if attn_impl in ("pallas", "pallas_interpret") and spec.causal:
        return flash(q, k, v, interpret=(attn_impl == "pallas_interpret"))
    if attn_impl == "auto" and flash_serves(
            "tpu", causal=spec.causal, cached=False, cross=False,
            attn_chunk=spec.attn_chunk, mesh_devices=_mesh_devices(q, mesh),
            head_dim=spec.head_dim, seq=s):
        # the branch is chosen where the program is lowered: the kernel
        # for a TPU, the XLA path for any other platform
        return jax.lax.platform_dependent(q, k, v, tpu=flash, default=xla)
    return xla(q, k, v)


def attention(params, x, spec: AttentionSpec, positions,
              attn_impl: str = "auto", kv_cache=None, cache_pos=None,
              cross_kv=None, mesh=None):
    """General attention entry point; returns (out [B,S,D], new_cache|None).

    attn_impl: "auto" (the Pallas flash kernel where `flash_serves`, else
    XLA), "xla", "pallas" or "pallas_interpret" (the kernels for causal
    self-attention and single-token decode, XLA elsewhere).

    - train / full self-attention: kv_cache is None.
    - prefill: kv_cache given, s > 1 -> attention over fresh k/v + cache fill.
    - decode: kv_cache given, s == 1 -> cached attention (sharded if the
      active partition rules shard the cache sequence axis).
    - cross attention: cross_kv = (k, v) from encoder states.
    """
    b, s, _ = x.shape
    rules = partition.active_rules()
    if cross_kv is not None:
        q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
        if spec.bias:
            q = q + params["bq"].astype(x.dtype)
        q = q.reshape(b, s, spec.n_heads, spec.head_dim)
        k, v = cross_kv
        if s == 1 and mesh is not None and rules is not None:
            out = sharded_cache_attention(q, k, v, spec, jnp.int32(0),
                                          mesh, rules, causal=False)
        elif spec.attn_chunk and s > spec.attn_chunk:
            out = _chunked_sdpa(q, k.astype(q.dtype), v.astype(q.dtype),
                                spec, 0, causal=False)
        else:
            out = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype), spec, None)
        new_cache = None
    elif kv_cache is None:
        q, k, v = _project_qkv(params, x, spec, positions)
        out = _self_attention(q, k, v, spec, attn_impl, mesh)
        new_cache = None
    else:
        q, k, v = _project_qkv(params, x, spec, positions)
        seq_sharded = (rules is not None and mesh is not None
                       and _flat_axes(rules.get("seq_kv")))
        if s == 1 and seq_sharded and \
                kv_cache["k"].shape[1] % _n_seq_shards(mesh, rules) == 0 \
                and attn_impl in ("xla", "auto"):
            out, k_cache, v_cache = sharded_cache_update_attention(
                q, k, v, kv_cache["k"], kv_cache["v"], spec, cache_pos,
                mesh, rules)
            out = out.reshape(b, s, spec.q_dim)
            y = jnp.einsum("bsh,hd->bsd", out,
                           params["wo"].astype(x.dtype))
            if spec.bias:
                y = y + params["bo"].astype(x.dtype)
            return y, {"k": k_cache, "v": v_cache}
        k_cache = jax.lax.dynamic_update_slice(
            kv_cache["k"], k.astype(kv_cache["k"].dtype), (0, cache_pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            kv_cache["v"], v.astype(kv_cache["v"].dtype), (0, cache_pos, 0, 0))
        if s > 1:
            # prefill: attend over the fresh k/v (== cache content)
            if spec.attn_chunk and s > spec.attn_chunk:
                out = _chunked_sdpa(q, k, v, spec, 0, causal=True)
            else:
                out = _sdpa(q, k, v, spec, causal_mask(s, s))
        elif attn_impl in ("pallas", "pallas_interpret"):
            from repro.kernels.decode_attention import ops as da_ops
            out = da_ops.decode_attention(
                q[:, 0], k_cache, v_cache, cache_pos + s, scale=spec.scale,
                interpret=(attn_impl == "pallas_interpret"))[:, None]
        elif mesh is not None and rules is not None:
            out = sharded_cache_attention(q, k_cache, v_cache, spec,
                                          cache_pos, mesh, rules)
        else:
            out = _local_cached_attention(q, k_cache, v_cache, spec,
                                          cache_pos)
        new_cache = {"k": k_cache, "v": v_cache}
    out = out.reshape(b, s, spec.q_dim)
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(x.dtype))
    # constrain right at the producer so the TP contraction lowers as a
    # reduce-scatter onto the sequence-parallel layout (not AR + slice)
    y = partition.constrain(y, ("batch", "seq", "embed_act"))
    if spec.bias:
        y = y + params["bo"].astype(x.dtype)
    return y, new_cache


def causal_mask(sq: int, sk: int, offset: int = 0) -> jax.Array:
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(sk)[None, :]
    return (ki <= qi)[None, None, None]


def cross_kv_from_encoder(params, enc: jax.Array, spec: AttentionSpec):
    b, se, _ = enc.shape
    k = jnp.einsum("bsd,dh->bsh", enc, params["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dh->bsh", enc, params["wv"].astype(enc.dtype))
    if spec.bias:
        v = v + params["bv"].astype(enc.dtype)
    return (k.reshape(b, se, spec.n_kv_heads, spec.head_dim),
            v.reshape(b, se, spec.n_kv_heads, spec.head_dim))


# ---------------------------------------------------------------------------
# Feed-forward
# ---------------------------------------------------------------------------


def swiglu_mlp(params, x: jax.Array) -> jax.Array:
    gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y = jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(x.dtype))
    return partition.constrain(y, ("batch", "seq", "embed_act"))


def gelu_mlp(params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
    if "b_up" in params:
        h = h + params["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    y = jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(x.dtype))
    y = partition.constrain(y, ("batch", "seq", "embed_act"))
    if "b_down" in params:
        y = y + params["b_down"].astype(x.dtype)
    return y


def mlp(params, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        return swiglu_mlp(params, x)
    if kind == "gelu":
        return gelu_mlp(params, x)
    raise ValueError(f"unknown mlp kind {kind!r}")
