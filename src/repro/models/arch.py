"""Architecture definitions: one ArchConfig covers all 10 assigned
families (dense / MoE / SSM / hybrid / enc-dec / VLM). Parameters are
plain nested dicts with per-layer leaves stacked on axis 0 so the depth
loop is a single ``lax.scan`` (O(1) HLO in depth — compile-time critical
for the 512-device dry-run).

Simplifications vs the exact HF checkpoints (documented in DESIGN.md):
pre-norm only (gemma2's extra post-norms folded), untied LM heads,
no dropout. Structural features that change the *system* shape — GQA
ratios, head dims, local/global alternation, logit softcaps, qk-norm,
MoE top-k routing + capacity, Mamba1/Mamba2 state shapes, shared
attention blocks, encoder-decoder cross-attention, VLM prefix — are all
implemented.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L

# When True, depth scans trace unrolled. Used ONLY by the dry-run's
# reduced-depth calibration compiles: XLA cost_analysis counts a while
# body once regardless of trip count, so calibration needs loop-free HLO.
SCAN_UNROLL = False


def _scan(f, init, xs):
    return jax.lax.scan(f, init, xs, unroll=True if SCAN_UNROLL else 1)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None         # default d_model // n_heads
    # attention flavor
    rope_theta: float = 10_000.0
    window: Optional[int] = None           # sliding window size
    alt_local_global: bool = False         # gemma2: even layers local
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    qk_norm: bool = False
    # moe
    n_experts: int = 0
    top_k: int = 0
    # ssm
    ssm_state: int = 0
    ssm_expand: int = 2
    mamba_version: int = 2
    # hybrid (zamba2): shared attention block every k layers
    shared_attn_every: int = 0
    # encdec
    n_enc_layers: int = 0
    enc_seq: int = 1500
    # vlm
    n_patches: int = 0
    # numerics
    act_dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def adt(self):
        return jnp.dtype(self.act_dtype)

    def param_count(self) -> int:
        """Total N (for MODEL_FLOPS accounting)."""
        return sum(int(x.size) for x in jax.tree.leaves(
            jax.eval_shape(lambda: init_params(self, jax.random.key(0)))))

    def active_param_count(self) -> int:
        """Active N per token (MoE counts top_k of n_experts experts)."""
        total = self.param_count()
        if self.family != "moe" or self.n_experts == 0:
            return total
        expert = 3 * self.d_model * self.d_ff * self.n_layers
        dense_part = total - self.n_experts * expert
        return dense_part + self.top_k * expert


# ------------------------------------------------------------------- init
def _norm(key, d):
    return jnp.zeros((d,), jnp.float32)


def _dense(key, shape, scale=None):
    scale = scale if scale is not None else (1.0 / (shape[0] ** 0.5))
    return jax.random.normal(key, shape, jnp.float32) * scale


def _attn_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 7)
    D, H, Kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    p = dict(
        ln=_norm(ks[0], D),
        wq=_dense(ks[1], (D, H * hd)),
        wk=_dense(ks[2], (D, Kv * hd)),
        wv=_dense(ks[3], (D, Kv * hd)),
        wo=_dense(ks[4], (H * hd, D)),
    )
    if cfg.qk_norm:
        p["q_norm"] = _norm(ks[5], hd)
        p["k_norm"] = _norm(ks[6], hd)
    return p


def _mlp_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    D, F = cfg.d_model, cfg.d_ff
    return dict(ln=_norm(ks[0], D), w_gate=_dense(ks[1], (D, F)),
                w_up=_dense(ks[2], (D, F)), w_down=_dense(ks[3], (F, D)))


def _moe_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 5)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return dict(ln=_norm(ks[0], D), router=_dense(ks[1], (D, E)),
                w_gate=_dense(ks[2], (E, D, F)), w_up=_dense(ks[3], (E, D, F)),
                w_down=_dense(ks[4], (E, F, D)))


def _mamba_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 8)
    D = cfg.d_model
    Di = cfg.ssm_expand * D
    N = cfg.ssm_state
    if cfg.mamba_version == 1:
        dt_rank = max(D // 16, 1)
        return dict(
            ln=_norm(ks[0], D),
            in_proj=_dense(ks[1], (D, 2 * Di)),
            conv_w=_dense(ks[2], (4, Di), scale=0.5),
            x_proj=_dense(ks[3], (Di, dt_rank + 2 * N)),
            dt_proj=_dense(ks[4], (dt_rank, Di)),
            A_log=jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32),
                                   (Di, 1))),
            D_skip=jnp.ones((Di,), jnp.float32),
            out_proj=_dense(ks[5], (Di, D)),
        )
    H = Di // 64                                  # head dim P = 64
    return dict(
        ln=_norm(ks[0], D),
        in_proj=_dense(ks[1], (D, 2 * Di + 2 * N + H)),
        conv_w=_dense(ks[2], (4, Di + 2 * N), scale=0.5),
        A_log=jnp.zeros((H,), jnp.float32),
        D_skip=jnp.ones((H,), jnp.float32),
        norm_scale=_norm(ks[3], Di),
        out_proj=_dense(ks[4], (Di, D)),
    )


def _layer_params(key, cfg: ArchConfig):
    """One decoder layer of the appropriate family."""
    k1, k2 = jax.random.split(key)
    if cfg.family in ("dense", "vlm"):
        return dict(attn=_attn_params(k1, cfg), mlp=_mlp_params(k2, cfg))
    if cfg.family == "moe":
        return dict(attn=_attn_params(k1, cfg), moe=_moe_params(k2, cfg))
    if cfg.family == "ssm":
        return dict(mamba=_mamba_params(k1, cfg))
    if cfg.family == "hybrid":
        return dict(mamba=_mamba_params(k1, cfg))
    if cfg.family == "encdec":
        k3 = jax.random.fold_in(k2, 3)
        return dict(attn=_attn_params(k1, cfg), mlp=_mlp_params(k2, cfg),
                    xattn=_attn_params(k3, cfg))
    raise ValueError(cfg.family)


def init_params(cfg: ArchConfig, key):
    keys = jax.random.split(key, 8)
    layer_keys = jax.random.split(keys[0], cfg.n_layers)
    p = dict(
        embed=_dense(keys[1], (cfg.vocab, cfg.d_model), scale=1.0),
        lm_head=_dense(keys[2], (cfg.vocab, cfg.d_model)),
        final_ln=_norm(keys[3], cfg.d_model),
        layers=jax.vmap(lambda k: _layer_params(k, cfg))(layer_keys),
    )
    if cfg.family == "hybrid" and cfg.shared_attn_every:
        p["shared_attn"] = _attn_params(keys[4], cfg)
    if cfg.family == "encdec":
        enc_keys = jax.random.split(keys[5], cfg.n_enc_layers)
        enc_cfg = dataclasses.replace(cfg, family="dense")
        p["enc_layers"] = jax.vmap(
            lambda k: _layer_params(k, enc_cfg))(enc_keys)
        p["enc_final_ln"] = _norm(keys[6], cfg.d_model)
    return p


# ----------------------------------------------------------------- forward
def _attn_apply(p, x, cfg: ArchConfig, *, layer_local: bool = False,
                kv_x=None, causal=True, positions=None, use_rope=True):
    """Full-sequence attention (train/prefill). kv_x: cross-attn source."""
    B, S, D = x.shape
    h = L.rms_norm(x, p["ln"])
    src = h if kv_x is None else kv_x
    q = jnp.einsum("bsd,de->bse", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,de->bse", src, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,de->bse", src, p["wv"].astype(h.dtype))
    Sk = src.shape[1]
    q = q.reshape(B, S, cfg.n_heads, cfg.hd)
    k = k.reshape(B, Sk, cfg.n_kv, cfg.hd)
    v = v.reshape(B, Sk, cfg.n_kv, cfg.hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k = L.rms_norm(k, p["k_norm"])
    if use_rope and kv_x is None:
        pos = positions if positions is not None else jnp.arange(S)[None]
        q = L.rope(q, pos, cfg.rope_theta)
        k = L.rope(k, pos, cfg.rope_theta)
    window = cfg.window if (cfg.window and layer_local) else None
    if window and S > 2 * window and S % window == 0 and kv_x is None:
        o = L.local_block_attention(q, k, v, window=window,
                                    softcap=cfg.attn_softcap)
    else:
        o = L.gqa_attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.attn_softcap)
    o = o.reshape(B, S, cfg.n_heads * cfg.hd)
    return x + jnp.einsum("bse,ed->bsd", o, p["wo"].astype(h.dtype))


def _mlp_apply(p, x):
    h = L.rms_norm(x, p["ln"])
    return x + L.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def _moe_apply(p, x, cfg: ArchConfig):
    h = L.rms_norm(x, p["ln"])
    return x + L.moe_block(h, p["router"], p["w_gate"], p["w_up"],
                           p["w_down"], top_k=cfg.top_k)


def _mamba_apply(p, x, cfg: ArchConfig):
    h = L.rms_norm(x, p["ln"])
    fn = L.mamba1_scan if cfg.mamba_version == 1 else L.mamba2_ssd
    return x + fn(h, p)


def _decoder_layer(cfg: ArchConfig, params, x, idx, enc=None, local=None):
    """One scanned decoder layer. ``local`` must be a *static* bool (the
    local/global alternation is handled by pair-scanning in forward())."""
    if cfg.family in ("dense", "vlm"):
        local = bool(cfg.window) if local is None else local
        x = _attn_apply(params["attn"], x, cfg, layer_local=local)
        x = _mlp_apply(params["mlp"], x)
    elif cfg.family == "moe":
        x = _attn_apply(params["attn"], x, cfg,
                        layer_local=bool(cfg.window))
        x = _moe_apply(params["moe"], x, cfg)
    elif cfg.family in ("ssm", "hybrid"):
        x = _mamba_apply(params["mamba"], x, cfg)
    elif cfg.family == "encdec":
        x = _attn_apply(params["attn"], x, cfg, use_rope=False)
        x = _attn_apply(params["xattn"], x, cfg, kv_x=enc, causal=False,
                        use_rope=False)
        x = _mlp_apply(params["mlp"], x)
    return x


def forward(params, cfg: ArchConfig, tokens, *, extra=None):
    """Training/prefill forward -> logits (B,S,V) in f32.

    ``extra``: family-specific stub inputs — vlm: (B,n_patches,D) patch
    embeddings; encdec: (B,enc_seq,D) precomputed frame embeddings.

    The layers leave intermediate shardings to the compiler's
    propagation. On a mesh whose axes are Explicit (``jax.make_mesh``'s
    default) every op would need a stated output sharding (the embedding
    gather alone would map ``data`` twice: batch and FSDP feature dim), so
    the forward runs with those axes made Auto; the logits keep the
    tokens' batch sharding.
    """
    sharding = jax.typeof(tokens).sharding
    if not sharding.mesh.explicit_axes:  # reprolint: ignore[TRC002] static mesh metadata
        return _forward(params, cfg, tokens, extra)
    return jax.sharding.auto_axes(
        lambda p, t, e: _forward(p, cfg, t, e),
        out_sharding=sharding.update(spec=P(*sharding.spec, None)),
    )(params, tokens, extra)


def _forward(params, cfg: ArchConfig, tokens, extra):
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.adt)
    if cfg.family == "dense" and cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.adt)

    if cfg.family == "vlm":
        x = jnp.concatenate([extra.astype(cfg.adt), x], axis=1)

    enc = None
    if cfg.family == "encdec":
        e = extra.astype(cfg.adt)
        def enc_layer(h, lp):
            h = _attn_apply(lp["attn"], h, cfg, causal=False, use_rope=False)
            h = _mlp_apply(lp["mlp"], h)
            return h, None
        e, _ = _scan(enc_layer, e, params["enc_layers"])
        enc = L.rms_norm(e, params["enc_final_ln"])

    shared = params.get("shared_attn")
    every = cfg.shared_attn_every

    if cfg.alt_local_global:
        # static local/global alternation: scan layer *pairs* (even layer
        # local sliding-window, odd layer global) — gemma2 style.
        def pair(carry, xs):
            h, = carry
            lp, idx = xs
            lp0 = jax.tree.map(lambda a: a[0], lp)
            lp1 = jax.tree.map(lambda a: a[1], lp)
            h = _decoder_layer(cfg, lp0, h, idx, enc=enc, local=True)
            h = _decoder_layer(cfg, lp1, h, idx, enc=enc, local=False)
            return (h,), None

        np2 = cfg.n_layers // 2
        lp_pairs = jax.tree.map(lambda a: a.reshape(np2, 2, *a.shape[1:]),
                                params["layers"])
        (x,), _ = _scan(jax.checkpoint(pair), (x,),
                        (lp_pairs, jnp.arange(np2)))
    else:
        def layer(carry, xs):
            h, = carry
            lp, idx = xs
            if shared is not None and every:
                h = jax.lax.cond(idx % every == 0,
                                 lambda v: _attn_apply(shared, v, cfg),
                                 lambda v: v, h)
            h = _decoder_layer(cfg, lp, h, idx, enc=enc)
            return (h,), None

        idxs = jnp.arange(cfg.n_layers)
        (x,), _ = _scan(jax.checkpoint(layer), (x,),
                        (params["layers"], idxs))

    if cfg.family == "vlm":
        x = x[:, cfg.n_patches:, :]

    x = L.rms_norm(x, params["final_ln"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits
