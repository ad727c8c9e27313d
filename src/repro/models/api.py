"""Unified model API for the module zoo.

Every architecture is described by a ModelConfig; a declarative *param table*
(path -> ParamSpec) is the single source of truth for parameter shapes,
dtypes, logical sharding axes and initializers.  From it we derive:

  - abstract_params(cfg)        ShapeDtypeStructs (dry-run, no allocation)
  - init_params(cfg, key)       concrete params (smoke tests / real training)
  - param_specs(cfg)            logical-axes pytree (-> PartitionSpecs)

Step builders (build_loss_fn / build_prefill_fn / build_decode_fn) close over
the config and are pure jit-able functions.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers, mamba as mamba_mod, moe as moe_mod


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int               # per-expert hidden width
    every: int = 1          # MoE FFN on every `every`-th layer (1 = all)
    capacity_factor: float = 1.25
    impl: str = "dense"     # "dense" | "ep"
    fsdp_experts: bool = False
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    headdim: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str             # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int               # dense FFN width (0 for pure-ssm / pure-moe)
    vocab: int
    # attention flavour
    qk_norm: bool = False
    rope_theta: float = 1e4
    attn_bias: bool = False
    mlp_kind: str = "swiglu"
    norm_kind: str = "rms"          # rms | layer
    tie_embeddings: bool = False
    # family extensions
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    attn_every: int = 0             # hybrid: 1 attn layer per this many
    n_enc_layers: int = 0           # encdec
    enc_seq: int = 1500             # stub audio frontend frames
    n_patches: int = 0              # vlm stub patches
    # numerics / impl
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    kv_dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"         # auto | xla | pallas | pallas_interpret
    ssd_impl: str = "xla"
    remat: str = "none"             # none | full | dots
    loss_chunk: int = 0             # 0 = unchunked final projection
    max_pos: int = 8192             # learned-pos table size (encdec only)
    logit_softcap: float = 0.0
    attn_chunk: int = 0             # q-block size for chunked attention
    attn_unroll: bool = False       # unroll q-block loop (dry-run cost mode)
    scan_layers: bool = True

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def padded_vocab(self) -> int:
        """Embedding tables padded to a shardable multiple (Megatron-style);
        cfg.vocab stays the logical vocabulary and padded logit slots are
        masked to -inf in unembed()."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def mamba_spec(self) -> mamba_mod.MambaSpec:
        s = self.ssm or SSMConfig()
        return mamba_mod.MambaSpec(
            d_model=self.d_model, d_state=s.d_state, headdim=s.headdim,
            expand=s.expand, n_groups=s.n_groups, conv_kernel=s.conv_kernel,
            chunk=s.chunk, ssd_impl=self.ssd_impl)

    @property
    def attn_spec(self) -> layers.AttentionSpec:
        return layers.AttentionSpec(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            qk_norm=self.qk_norm, causal=True,
            use_rope=(self.family != "encdec"), bias=self.attn_bias,
            attn_chunk=self.attn_chunk, attn_unroll=self.attn_unroll)

    def layer_plan(self):
        """Returns (n_groups, per-group sub-layer plan).

        Each sub-layer is (mixer, ffn) with mixer in {attn, mamba} and ffn in
        {dense, moe, none}.  Homogeneous families have a 1-sub-layer plan
        scanned n_layers times; jamba scans super-blocks.
        """
        if self.family in ("dense", "vlm"):
            return self.n_layers, [("attn", "dense")]
        if self.family == "moe":
            assert self.moe is not None
            plan = [("attn", "moe" if (i % self.moe.every == 0) else "dense")
                    for i in range(self.moe.every)]
            assert self.n_layers % self.moe.every == 0
            return self.n_layers // self.moe.every, plan
        if self.family == "ssm":
            return self.n_layers, [("mamba", "none")]
        if self.family == "hybrid":
            assert self.attn_every > 0 and self.moe is not None
            period = self.attn_every
            attn_pos = period // 2
            plan = []
            for i in range(period):
                mixer = "attn" if i == attn_pos else "mamba"
                ffn = "moe" if (i % self.moe.every == 1) else "dense"
                plan.append((mixer, ffn))
            assert self.n_layers % period == 0
            return self.n_layers // period, plan
        if self.family == "encdec":
            return self.n_layers, [("attn", "dense")]   # decoder plan
        raise ValueError(self.family)


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str               # train | prefill | decode


SHAPE_CELLS = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Param table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]    # logical axis names, len == len(shape)
    init: str = "normal"            # normal|zeros|ones|a_log|dt_bias
    dtype: Any = None               # None -> cfg.param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _attn_table(cfg: ModelConfig, cross: bool = False) -> dict:
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim
    d = cfg.d_model
    t = {
        "wq": ParamSpec((d, hq), ("embed", "q_proj")),
        "wk": ParamSpec((d, hkv), ("embed", "kv_proj")),
        "wv": ParamSpec((d, hkv), ("embed", "kv_proj")),
        "wo": ParamSpec((hq, d), ("q_proj", "embed")),
    }
    if cfg.attn_bias:
        t["bq"] = ParamSpec((hq,), ("q_proj",), "zeros")
        t["bv"] = ParamSpec((hkv,), ("kv_proj",), "zeros")
        t["bo"] = ParamSpec((d,), ("embed",), "zeros")
    if cfg.qk_norm and not cross:
        t["q_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones")
        t["k_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones")
    return t


def _mlp_table(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind == "swiglu":
        return {
            "w_gate": ParamSpec((d, f), ("embed", "mlp")),
            "w_up": ParamSpec((d, f), ("embed", "mlp")),
            "w_down": ParamSpec((f, d), ("mlp", "embed")),
        }
    t = {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }
    if cfg.attn_bias:   # whisper-style biases everywhere
        t["b_up"] = ParamSpec((f,), ("mlp",), "zeros")
        t["b_down"] = ParamSpec((d,), ("embed",), "zeros")
    return t


def _moe_table(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    return {
        "w_router": ParamSpec((d, m.n_experts), ("embed", None)),
        "w1": ParamSpec((m.n_experts, d, m.d_ff),
                        ("expert", "embed_nofsdp" if not m.fsdp_experts
                         else "embed", "expert_mlp")),
        "w3": ParamSpec((m.n_experts, d, m.d_ff),
                        ("expert", "embed_nofsdp" if not m.fsdp_experts
                         else "embed", "expert_mlp")),
        "w2": ParamSpec((m.n_experts, m.d_ff, d),
                        ("expert", "expert_mlp",
                         "embed_nofsdp" if not m.fsdp_experts else "embed")),
    }


def _mamba_table(cfg: ModelConfig) -> dict:
    s = cfg.mamba_spec
    d = cfg.d_model
    return {
        "w_z": ParamSpec((d, s.d_inner), ("embed", "inner")),
        "w_x": ParamSpec((d, s.d_inner), ("embed", "inner")),
        "w_bc": ParamSpec((d, s.bc_dim), ("embed", None)),
        "w_dt": ParamSpec((d, s.n_heads), ("embed", "heads_ssm")),
        "dt_bias": ParamSpec((s.n_heads,), ("heads_ssm",), "dt_bias"),
        "a_log": ParamSpec((s.n_heads,), ("heads_ssm",), "a_log"),
        "d_skip": ParamSpec((s.n_heads,), ("heads_ssm",), "ones"),
        "w_conv_x": ParamSpec((s.conv_kernel, s.d_inner), (None, "inner")),
        "b_conv_x": ParamSpec((s.d_inner,), ("inner",), "zeros"),
        "w_conv_bc": ParamSpec((s.conv_kernel, s.bc_dim), (None, None)),
        "b_conv_bc": ParamSpec((s.bc_dim,), (None,), "zeros"),
        "norm_w": ParamSpec((s.d_inner,), ("inner",), "ones"),
        "w_out": ParamSpec((s.d_inner, d), ("inner", "embed")),
    }


def _norm_table(cfg: ModelConfig, name: str) -> dict:
    t = {f"{name}_w": ParamSpec((cfg.d_model,), ("embed",), "ones")}
    if cfg.norm_kind == "layer":
        t[f"{name}_b"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
    return t


def _sublayer_table(cfg: ModelConfig, mixer: str, ffn: str,
                    cross: bool = False) -> dict:
    t = {}
    t.update(_norm_table(cfg, "ln1"))
    if mixer == "attn":
        t["attn"] = _attn_table(cfg)
    else:
        t["mamba"] = _mamba_table(cfg)
    if cross:
        t.update(_norm_table(cfg, "lnx"))
        t["xattn"] = _attn_table(cfg, cross=True)
    if ffn != "none":
        t.update(_norm_table(cfg, "ln2"))
        if ffn == "dense":
            t["mlp"] = _mlp_table(cfg)
        else:
            t["moe"] = _moe_table(cfg)
    return t


def _stack_specs(tree: dict, n: int) -> dict:
    """Prepend a scanned `layers` axis of size n to every spec in tree."""
    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype)
    return jax.tree.map(f, tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_table(cfg: ModelConfig) -> dict:
    n_groups, plan = cfg.layer_plan()
    group = {}
    for i, (mixer, ffn) in enumerate(plan):
        group[f"sub{i}"] = _sublayer_table(
            cfg, mixer, ffn, cross=(cfg.family == "encdec"))
    table = {
        "embed": {"tok": ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed"))},
        "blocks": _stack_specs(group, n_groups),
    }
    table.update({"final": _norm_table(cfg, "lnf")})
    if not cfg.tie_embeddings:
        table["lm_head"] = ParamSpec((cfg.d_model, cfg.padded_vocab),
                                     ("embed", "vocab"))
    if cfg.family == "encdec":
        enc = {"sub0": _sublayer_table(
            dataclasses.replace(cfg), "attn", "dense")}
        table["enc_blocks"] = _stack_specs(enc, cfg.n_enc_layers)
        table["enc_final"] = _norm_table(cfg, "lnf")
        table["dec_pos"] = ParamSpec((cfg.max_pos, cfg.d_model),
                                     (None, "embed"))
    return table


def _is_spec(x):
    return isinstance(x, ParamSpec)


def abstract_params(cfg: ModelConfig):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or cfg.param_dtype),
        param_table(cfg), is_leaf=_is_spec)


def param_specs(cfg: ModelConfig):
    """Pytree of logical-axes tuples, mirroring params."""
    return jax.tree.map(lambda s: s.axes, param_table(cfg), is_leaf=_is_spec)


def _init_leaf(spec: ParamSpec, key, cfg: ModelConfig):
    dtype = spec.dtype or cfg.param_dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "a_log":
        h = spec.shape[-1]
        v = jnp.log(jnp.linspace(1.0, 16.0, h))
        return jnp.broadcast_to(v, spec.shape).astype(dtype)
    if spec.init == "dt_bias":
        h = spec.shape[-1]
        dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), h))
        v = jnp.log(jnp.expm1(dt))
        return jnp.broadcast_to(v, spec.shape).astype(dtype)
    # truncated-normal fan-in init
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = min(0.02, (1.0 / max(fan_in, 1)) ** 0.5)
    return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape,
                                        jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array):
    table = param_table(cfg)
    flat, treedef = jax.tree.flatten_with_path(
        table, is_leaf=_is_spec)
    leaves = []
    for path, spec in flat:
        pstr = "/".join(str(p) for p in path)
        # crc32, not hash(): str hashes change from process to process
        k = jax.random.fold_in(key, zlib.crc32(pstr.encode()) % (2 ** 31))
        leaves.append(_init_leaf(spec, k, cfg))
    return jax.tree.unflatten(treedef, leaves)


def param_count(cfg: ModelConfig) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        param_table(cfg), is_leaf=_is_spec))


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE counts top_k of n_experts)."""
    total = 0
    for s in jax.tree.leaves(param_table(cfg), is_leaf=_is_spec):
        n = int(np.prod(s.shape))
        total += n
    if cfg.moe is not None:
        n_groups, plan = cfg.layer_plan()
        m = cfg.moe
        expert_params = 3 * cfg.d_model * m.d_ff
        n_moe_layers = sum(1 for _, f in plan if f == "moe") * n_groups
        total -= n_moe_layers * expert_params * (m.n_experts - m.top_k)
    return total
