"""Decoder-only LM family: llama/mistral-style dense, MoE (DeepSeek),
MLA attention (DeepSeek-V3), prefix-LM VLM backbone (PaliGemma).

One configurable family = one code path exercised by 7 of the 10 assigned
architectures.  Written scan-over-layers with stacked params so the fused
AdaLomo backward (core/fused.py) applies; also provides prefill/decode
serving steps with ring-buffer KV caches (bounded cache for SWA archs —
what makes danube long_500k sub-quadratic).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.moe import MoEConfig, capacity, moe_ffn, moe_init
from repro.sharding.act import shard_act

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None
    norm: str = "rmsnorm"
    qk_norm: bool = False
    window: Optional[int] = None          # SWA
    rope_theta: float = 10000.0
    rope_pct: float = 1.0
    act: str = "silu"
    glu: bool = True
    tie_embeddings: bool = False
    embed_scale: bool = False             # gemma-style sqrt(d) embed scaling
    # prefix-LM / stub modality frontend (paligemma)
    prefix_lm: bool = False
    n_prefix_tokens: int = 0              # stub patch/frame embeds prepended
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mtp: bool = False                     # deepseek-v3 multi-token prediction
    mtp_weight: float = 0.1
    z_loss: float = 0.0
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    def param_count(self) -> int:
        """Total parameters (for 6·N·D roofline bookkeeping)."""
        import math
        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), self))
        return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Active params per token (MoE: shared + top-k routed only)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        E, K, f, d = (self.moe.n_routed, self.moe.top_k,
                      self.moe.d_ff_expert, self.d_model)
        routed = self.n_layers * E * 3 * d * f
        active_routed = self.n_layers * K * 3 * d * f
        return total - routed + active_routed


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _attn_init(key, cfg: LMConfig) -> dict:
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 10)
    dt = cfg.dtype
    if cfg.mla is not None:
        m = cfg.mla
        p = {
            "w_dq": L.linear_init(ks[0], d, m.q_lora_rank, dtype=dt),
            "q_ln": L.norm_init(m.q_lora_rank, "rmsnorm"),
            "w_uq": L.linear_init(ks[1], m.q_lora_rank,
                                  H * (m.d_nope + m.d_rope), dtype=dt),
            "w_dkv": L.linear_init(ks[2], d, m.kv_lora_rank, dtype=dt),
            "kv_ln": L.norm_init(m.kv_lora_rank, "rmsnorm"),
            "w_kr": L.linear_init(ks[3], d, m.d_rope, dtype=dt),
            "w_uk": L.linear_init(ks[4], m.kv_lora_rank, H * m.d_nope,
                                  dtype=dt),
            "w_uv": L.linear_init(ks[5], m.kv_lora_rank, H * m.d_v, dtype=dt),
            "wo": L.linear_init(ks[6], H * m.d_v, d,
                                scale=(2 * cfg.n_layers) ** -0.5, dtype=dt),
        }
        return p
    p = {
        "wq": L.linear_init(ks[0], d, H * dh, dtype=dt),
        "wk": L.linear_init(ks[1], d, K * dh, dtype=dt),
        "wv": L.linear_init(ks[2], d, K * dh, dtype=dt),
        "wo": L.linear_init(ks[3], H * dh, d,
                            scale=(2 * cfg.n_layers) ** -0.5, dtype=dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.norm_init(dh, "rmsnorm")
        p["k_norm"] = L.norm_init(dh, "rmsnorm")
    return p


def _block_init(key, cfg: LMConfig) -> dict:
    ks = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.dtype
    p = {
        "ln1": L.norm_init(d, cfg.norm),
        "ln2": L.norm_init(d, cfg.norm),
        "attn": _attn_init(ks[0], cfg),
    }
    if cfg.moe is not None:
        p["moe"] = moe_init(ks[1], d, cfg.moe, dtype=dt)
    elif cfg.glu:
        p["mlp"] = {
            "w_gate": L.linear_init(ks[1], d, f, dtype=dt),
            "w_up": L.linear_init(ks[2], d, f, dtype=dt),
            "w_down": L.linear_init(ks[3], f, d,
                                    scale=(2 * cfg.n_layers) ** -0.5,
                                    dtype=dt),
        }
    else:
        p["mlp"] = {
            "w_up": L.linear_init(ks[1], d, f, dtype=dt),
            "b_up": jnp.zeros((f,), dt),
            "w_down": L.linear_init(ks[2], f, d, dtype=dt),
            "b_down": jnp.zeros((d,), dt),
        }
    return p


def init_params(key, cfg: LMConfig) -> dict:
    """Params in the fused-engine layout: {outer, shared, stacks}."""
    k_e, k_b, k_h, k_m = jax.random.split(key, 4)
    outer = {
        "tok_embed": L.embed_init(k_e, cfg.vocab, cfg.d_model,
                                  dtype=cfg.dtype),
        "final_norm": L.norm_init(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        outer["head"] = L.linear_init(k_h, cfg.d_model, cfg.vocab,
                                      dtype=cfg.dtype)
    if cfg.mtp:
        # MTP block is dense (the routed experts live in the main stack).
        mtp_cfg = dataclasses.replace(cfg, moe=None, mtp=False)
        outer["mtp_proj"] = L.linear_init(k_m, 2 * cfg.d_model, cfg.d_model,
                                          dtype=cfg.dtype)
        outer["mtp_block"] = _block_init(k_m, mtp_cfg)
        outer["mtp_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    blocks = jax.vmap(lambda k: _block_init(k, cfg))(
        jax.random.split(k_b, cfg.n_layers))
    return {"outer": outer, "shared": {}, "stacks": {"blocks": blocks}}


# --------------------------------------------------------------------------
# Attention paths
# --------------------------------------------------------------------------

def _gqa_attn(p: dict, cfg: LMConfig, h: Array, pos: Array,
              prefix_len: Optional[Array],
              seg: Optional[Array] = None) -> Array:
    B, S, _ = h.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = shard_act(L.dense(h, p["wq"]).reshape(B, S, H, dh), "heads")
    k = shard_act(L.dense(h, p["wk"]).reshape(B, S, K, dh), "heads")
    v = shard_act(L.dense(h, p["wv"]).reshape(B, S, K, dh), "heads")
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"]["scale"])
        k = L.rmsnorm(k, p["k_norm"]["scale"])
    d_rot = int(dh * cfg.rope_pct) // 2 * 2
    # packed batches: pos is (B, S) with per-segment restarts, so RoPE
    # phases restart at each document boundary (sin/cos broadcast per row)
    sin, cos = L.rope_sincos(pos, d_rot, cfg.rope_theta)
    q = L.apply_rope(q, sin, cos, cfg.rope_pct)
    k = L.apply_rope(k, sin, cos, cfg.rope_pct)
    spec = L.MaskSpec(causal=True, window=cfg.window,
                      has_prefix=cfg.prefix_lm, segmented=seg is not None)
    o = L.attention(q, k, v, spec=spec, q_pos=pos, kv_pos=pos,
                    prefix_len=prefix_len, q_seg=seg, kv_seg=seg)
    o = shard_act(o, "heads")
    return shard_act(L.dense(o.reshape(B, S, H * dh), p["wo"]), "hidden")


def _mla_attn(p: dict, cfg: LMConfig, h: Array, pos: Array,
              prefix_len: Optional[Array],
              seg: Optional[Array] = None) -> Array:
    """MLA (train/prefill path): latent KV is up-projected per head."""
    m = cfg.mla
    B, S, _ = h.shape
    H = cfg.n_heads
    q = shard_act(
        L.dense(L.rmsnorm(L.dense(h, p["w_dq"]), p["q_ln"]["scale"]),
                p["w_uq"]).reshape(B, S, H, m.d_nope + m.d_rope), "heads")
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    ckv = L.rmsnorm(L.dense(h, p["w_dkv"]), p["kv_ln"]["scale"])  # [B,S,r]
    k_rope = L.dense(h, p["w_kr"]).reshape(B, S, 1, m.d_rope)
    sin, cos = L.rope_sincos(pos, m.d_rope, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, sin, cos)
    k_rope = L.apply_rope(k_rope, sin, cos)
    k_nope = shard_act(L.dense(ckv, p["w_uk"]).reshape(B, S, H, m.d_nope),
                       "heads")
    v = shard_act(L.dense(ckv, p["w_uv"]).reshape(B, S, H, m.d_v), "heads")
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (B, S, H, m.d_rope))],
                        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    spec = L.MaskSpec(causal=True, window=cfg.window,
                      has_prefix=cfg.prefix_lm, segmented=seg is not None)
    scale = (m.d_nope + m.d_rope) ** -0.5
    o = shard_act(L.attention(qf, k, v, spec=spec, q_pos=pos, kv_pos=pos,
                              prefix_len=prefix_len, q_seg=seg, kv_seg=seg,
                              scale=scale), "heads")
    return shard_act(L.dense(o.reshape(B, S, H * m.d_v), p["wo"]), "hidden")


# --------------------------------------------------------------------------
# Fused-engine spec (train path)
# --------------------------------------------------------------------------

def make_block_body(cfg: LMConfig):
    def body(p, ctx, carry, aux_idx):
        del aux_idx
        _, ctx_act = ctx
        x, aux_loss = carry
        pos = jax.lax.stop_gradient(ctx_act["pos"]).astype(jnp.int32)
        prefix_len = ctx_act.get("prefix")
        if prefix_len is not None:
            prefix_len = jax.lax.stop_gradient(prefix_len).astype(jnp.int32)
        seg = ctx_act.get("seg")
        if seg is not None:
            seg = jax.lax.stop_gradient(seg).astype(jnp.int32)
        h = L.norm_apply(p["ln1"], x, kind=cfg.norm)
        if cfg.mla is not None:
            x = x + _mla_attn(p["attn"], cfg, h, pos, prefix_len, seg)
        else:
            x = x + _gqa_attn(p["attn"], cfg, h, pos, prefix_len, seg)
        h = L.norm_apply(p["ln2"], x, kind=cfg.norm)
        if cfg.moe is not None:
            y, aux = moe_ffn(p["moe"], h, cfg.moe)
            x = x + y
            aux_loss = aux_loss + aux
        elif cfg.glu:
            x = x + L.glu_mlp(p["mlp"], h, cfg.act)
        else:
            x = x + L.mlp(p["mlp"], h, cfg.act)
        return (x, aux_loss)

    return body


def _embed(outer: dict, cfg: LMConfig, tokens: Array) -> Array:
    x = outer["tok_embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits(outer: dict, cfg: LMConfig, h: Array) -> Array:
    w = outer["tok_embed"].T if cfg.tie_embeddings else outer["head"]
    return shard_act(jnp.einsum("...d,dv->...v", h, w,
                                preferred_element_type=jnp.float32),
                     "vocab")


def cross_entropy(logits: Array, labels: Array, z_loss: float = 0.0
                  ) -> tuple[Array, Array, Array]:
    """Masked CE. labels < 0 are ignored. Returns (loss, ntok, ncorrect)."""
    mask = (labels >= 0)
    lab = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    loss = jnp.sum(nll)
    if z_loss:
        loss = loss + z_loss * jnp.sum(jnp.square(lse) * mask)
    ntok = jnp.sum(mask)
    correct = jnp.sum((jnp.argmax(logits, axis=-1) == lab) & mask)
    return loss, ntok, correct


def make_prologue(cfg: LMConfig):
    def prologue(outer, batch):
        x = _embed(outer, cfg, batch["tokens"])
        if cfg.n_prefix_tokens:
            # stub modality frontend: precomputed patch/frame embeddings
            x = jnp.concatenate(
                [batch["prefix_embed"].astype(x.dtype), x], axis=1)
        return (x, jnp.zeros((), jnp.float32))

    return prologue


def make_pro_ctx(cfg: LMConfig):
    def pro_ctx(outer, batch):
        # ctx activations are float32 so the fused engine's generic
        # zero-cotangent plumbing stays vjp-safe; bodies stop_gradient
        # and cast back to int32.
        if "segment_ids" in batch:
            if cfg.prefix_lm or cfg.n_prefix_tokens or cfg.mtp:
                raise ValueError(
                    "packed (segment-id) batches are not supported for "
                    "prefix-LM / modality-prefix / MTP architectures")
            return {"pos": batch["positions"].astype(jnp.float32),
                    "seg": batch["segment_ids"].astype(jnp.float32)}
        S = batch["tokens"].shape[1] + cfg.n_prefix_tokens
        ctx = {"pos": jnp.arange(S, dtype=jnp.float32)}
        if cfg.prefix_lm:
            ctx["prefix"] = batch["prefix_len"].astype(jnp.float32)
        return ctx

    return pro_ctx


def make_epilogue(cfg: LMConfig):
    def epilogue(outer, carry, batch):
        x, aux_loss = carry
        if cfg.n_prefix_tokens:
            x = x[:, cfg.n_prefix_tokens:]
        h = L.norm_apply(outer["final_norm"], x, kind=cfg.norm)
        logits = _logits(outer, cfg, h)
        loss_sum, ntok, correct = cross_entropy(logits, batch["labels"],
                                                cfg.z_loss)
        denom = jnp.maximum(ntok, 1).astype(jnp.float32)
        loss = loss_sum / denom + aux_loss
        if cfg.mtp:
            # Multi-token prediction (deepseek-v3): one extra block predicts
            # token t+2 from [h_t ; emb(token_{t+1})].
            emb_next = _embed(outer, cfg, batch["tokens"])
            mtp_in = jnp.concatenate([h, emb_next], axis=-1)
            hm = L.dense(mtp_in, outer["mtp_proj"])
            body = make_block_body(
                dataclasses.replace(cfg, mtp=False, moe=None))
            S = hm.shape[1]
            ctx = ({}, {"pos": jnp.arange(S, dtype=jnp.float32)})
            hm, _ = body(outer["mtp_block"], ctx,
                         (hm, jnp.zeros((), jnp.float32)), 0)
            hm = L.norm_apply(outer["mtp_norm"], hm, kind=cfg.norm)
            mtp_logits = _logits(outer, cfg, hm)
            mtp_loss, mtp_ntok, _ = cross_entropy(mtp_logits,
                                                  batch["labels_mtp"])
            loss = loss + cfg.mtp_weight * mtp_loss / jnp.maximum(
                mtp_ntok, 1).astype(jnp.float32)
        metrics = jax.lax.stop_gradient({
            "loss": loss,
            "ntokens": ntok.astype(jnp.float32),
            "accuracy": correct.astype(jnp.float32) / denom,
        })
        return loss, metrics

    return epilogue


def make_fused_spec(cfg: LMConfig):
    from repro.core.fused import FusedSpec
    return FusedSpec(
        prologue=make_prologue(cfg),
        bodies={"blocks": make_block_body(cfg)},
        epilogue=make_epilogue(cfg),
        pro_ctx=make_pro_ctx(cfg),
    )


# --------------------------------------------------------------------------
# Serving: prefill + single-token decode with (ring) KV cache
# --------------------------------------------------------------------------

def cache_window(cfg: LMConfig, max_len: int) -> int:
    """SWA archs only ever need a window-sized ring cache."""
    return min(cfg.window, max_len) if cfg.window else max_len


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> dict:
    W = cache_window(cfg, max_len)
    Lr, dt = cfg.n_layers, cfg.dtype
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((Lr, batch, W, m.kv_lora_rank), dt),
            "kr": jnp.zeros((Lr, batch, W, m.d_rope), dt),
            "pos": jnp.full((W,), -1, jnp.int32),
            "cur": jnp.zeros((), jnp.int32),
        }
    K, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((Lr, batch, W, K, dh), dt),
        "v": jnp.zeros((Lr, batch, W, K, dh), dt),
        "pos": jnp.full((W,), -1, jnp.int32),
        "cur": jnp.zeros((), jnp.int32),
    }


def _decode_gqa(p, cfg: LMConfig, h, kc, vc, pos_tab, cur):
    """One-token GQA decode; writes ring slot cur % W. h: [B,1,d]."""
    B = h.shape[0]
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L.dense(h, p["wq"]).reshape(B, 1, H, dh)
    k = L.dense(h, p["wk"]).reshape(B, 1, K, dh)
    v = L.dense(h, p["wv"]).reshape(B, 1, K, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"]["scale"])
        k = L.rmsnorm(k, p["k_norm"]["scale"])
    posv = cur[None].astype(jnp.float32)
    d_rot = int(dh * cfg.rope_pct) // 2 * 2
    sin, cos = L.rope_sincos(posv, d_rot, cfg.rope_theta)
    q = L.apply_rope(q, sin, cos, cfg.rope_pct)
    k = L.apply_rope(k, sin, cos, cfg.rope_pct)
    W = kc.shape[1]
    slot = jnp.mod(cur, W)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k, slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v, slot, axis=1)
    kv_pos = jnp.broadcast_to(pos_tab[None], (B, W))
    o = L.decode_attention(q, kc, vc, kv_pos=kv_pos,
                           q_pos=jnp.full((B,), cur, jnp.int32),
                           window=cfg.window)
    return L.dense(o.reshape(B, 1, H * dh), p["wo"]), kc, vc


def _decode_mla(p, cfg: LMConfig, h, ckv_c, kr_c, pos_tab, cur):
    """Absorbed-matmul MLA decode: scores in latent space, cache = latent."""
    m = cfg.mla
    B = h.shape[0]
    H = cfg.n_heads
    q = L.dense(L.rmsnorm(L.dense(h, p["w_dq"]), p["q_ln"]["scale"]),
                p["w_uq"]).reshape(B, 1, H, m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    posv = cur[None].astype(jnp.float32)
    sin, cos = L.rope_sincos(posv, m.d_rope, cfg.rope_theta)
    q_rope = L.apply_rope(q_rope, sin, cos)
    ckv = L.rmsnorm(L.dense(h, p["w_dkv"]), p["kv_ln"]["scale"])  # [B,1,r]
    kr = L.dense(h, p["w_kr"]).reshape(B, 1, 1, m.d_rope)
    kr = L.apply_rope(kr, sin, cos).reshape(B, 1, m.d_rope)
    W = ckv_c.shape[1]
    slot = jnp.mod(cur, W)
    ckv_c = jax.lax.dynamic_update_slice_in_dim(ckv_c, ckv, slot, axis=1)
    kr_c = jax.lax.dynamic_update_slice_in_dim(kr_c, kr, slot, axis=1)
    # absorb W_uk into the query: q_lat [B,H,r]
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.d_nope)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    s_nope = jnp.einsum("bhr,bwr->bhw", q_lat, ckv_c)
    s_rope = jnp.einsum("bhd,bwd->bhw", q_rope[:, 0], kr_c)
    scale = (m.d_nope + m.d_rope) ** -0.5
    logits = (s_nope + s_rope).astype(jnp.float32) * scale
    valid = (pos_tab >= 0) & (pos_tab <= cur)
    logits = jnp.where(valid[None, None, :], logits, L.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(ckv_c.dtype)
    o_lat = jnp.einsum("bhw,bwr->bhr", probs, ckv_c)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.d_v)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv).reshape(B, 1, H * m.d_v)
    return L.dense(o, p["wo"]), ckv_c, kr_c


def make_decode_step(cfg: LMConfig):
    """decode_step(params, cache, batch{'tokens': (B,1)}) -> (logits, cache)."""
    def decode_step(params, cache, batch):
        outer = params["outer"]
        x = _embed(outer, cfg, batch["tokens"])  # [B,1,d]
        cur = cache["cur"]
        W0 = cache["pos"].shape[0]
        # mark the current slot *before* attention so the token sees itself
        cache = dict(cache)
        cache["pos"] = cache["pos"].at[jnp.mod(cur, W0)].set(cur)
        aux0 = jnp.zeros((), jnp.float32)

        def body(carry, xs):
            x, _ = carry
            if cfg.mla is not None:
                layer_p, ckv_c, kr_c = xs
                h = L.norm_apply(layer_p["ln1"], x, kind=cfg.norm)
                a, ckv_c, kr_c = _decode_mla(layer_p["attn"], cfg, h,
                                             ckv_c, kr_c, cache["pos"], cur)
                new_slices = (ckv_c, kr_c)
            else:
                layer_p, kc, vc = xs
                h = L.norm_apply(layer_p["ln1"], x, kind=cfg.norm)
                a, kc, vc = _decode_gqa(layer_p["attn"], cfg, h, kc, vc,
                                        cache["pos"], cur)
                new_slices = (kc, vc)
            x = x + a
            h = L.norm_apply(layer_p["ln2"], x, kind=cfg.norm)
            if cfg.moe is not None:
                y, _ = moe_ffn(layer_p["moe"], h, cfg.moe)
                x = x + y
            elif cfg.glu:
                x = x + L.glu_mlp(layer_p["mlp"], h, cfg.act)
            else:
                x = x + L.mlp(layer_p["mlp"], h, cfg.act)
            return (x, aux0), new_slices

        blocks = params["stacks"]["blocks"]
        if cfg.mla is not None:
            xs = (blocks, cache["ckv"], cache["kr"])
        else:
            xs = (blocks, cache["k"], cache["v"])
        (x, _), new_cache_stk = jax.lax.scan(body, (x, aux0), xs)
        h = L.norm_apply(outer["final_norm"], x, kind=cfg.norm)
        logits = _logits(outer, cfg, h)[:, 0]
        if cfg.mla is not None:
            new_cache = {"ckv": new_cache_stk[0], "kr": new_cache_stk[1],
                         "pos": cache["pos"], "cur": cur + 1}
        else:
            new_cache = {"k": new_cache_stk[0], "v": new_cache_stk[1],
                         "pos": cache["pos"], "cur": cur + 1}
        return logits, new_cache

    return decode_step


# --------------------------------------------------------------------------
# Paged serving: prefill emits full per-layer K/V; decode reads/writes a
# shared page pool through per-sequence block tables (serve/paging.py).
# --------------------------------------------------------------------------

def make_prefill_kv_step(cfg: LMConfig):
    """prefill(params, batch{'tokens': [B,S], 'length': [B]}) ->
    (logits [B,vocab] at position length-1, k [L,B,S,K,dh], v [L,B,S,K,dh]).

    Unlike :func:`make_prefill_step` this keeps the *full* per-layer K/V
    (no ring truncation) so the engine can scatter it into KV pages; SWA is
    enforced by the decode-attention mask instead of cache truncation.
    Right-padding is harmless: with a causal mask, K/V at positions < length
    never see the pad tail, and logits are gathered at length-1."""
    assert cfg.mla is None, "paged serving supports GQA caches only"
    assert not cfg.prefix_lm, "paged serving: prefix-LM not plumbed yet"

    def prefill(params, batch):
        outer = params["outer"]
        tokens = batch["tokens"]
        length = batch["length"].astype(jnp.int32)
        B, S = tokens.shape
        x = _embed(outer, cfg, tokens)
        pos = jnp.arange(S, dtype=jnp.int32)
        body_train = make_block_body(cfg)

        def body(carry, layer_p):
            x, aux = carry
            ctx = ({}, {"pos": pos.astype(jnp.float32)})
            x2, aux2 = body_train(layer_p, ctx, (x, aux), 0)
            h = L.norm_apply(layer_p["ln1"], x, kind=cfg.norm)
            K, dh = cfg.n_kv_heads, cfg.head_dim
            k = L.dense(h, layer_p["attn"]["wk"]).reshape(B, S, K, dh)
            if cfg.qk_norm:
                k = L.rmsnorm(k, layer_p["attn"]["k_norm"]["scale"])
            d_rot = int(dh * cfg.rope_pct) // 2 * 2
            sin, cos = L.rope_sincos(pos.astype(jnp.float32), d_rot,
                                     cfg.rope_theta)
            k = L.apply_rope(k, sin, cos, cfg.rope_pct)
            v = L.dense(h, layer_p["attn"]["wv"]).reshape(B, S, K, dh)
            return (x2, aux2), (k, v)

        (x, _), (k_stk, v_stk) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            params["stacks"]["blocks"])
        x_last = jnp.take_along_axis(
            x, jnp.maximum(length - 1, 0)[:, None, None], axis=1)
        h = L.norm_apply(outer["final_norm"], x_last, kind=cfg.norm)
        logits = _logits(outer, cfg, h)[:, 0]
        return logits, k_stk, v_stk

    return prefill


def make_paged_decode_step(cfg: LMConfig, *, use_kernel=None,
                           interpret=False):
    """decode(params, pages, batch) -> (logits [B,vocab], new pages).

    pages: {'k','v': [L, N, K, ps, dh]} — the shared page pool.
    batch: tokens [B,1]; block_tables [B,P] (page ids, logical order,
    unallocated tail = scratch page 0); seq_lens [B] tokens already cached
    (== position of the incoming token); emit [B] bool — rows that are
    live this step.  Frozen rows write their K/V to the scratch page and
    their logits are garbage by construction; the engine masks them."""
    assert cfg.mla is None, "paged serving supports GQA caches only"
    from repro.kernels.decode_attention.ops import paged_decode_attention

    def decode(params, pages, batch):
        outer = params["outer"]
        tokens = batch["tokens"]
        bt = batch["block_tables"].astype(jnp.int32)
        n = batch["seq_lens"].astype(jnp.int32)            # [B]
        emit = batch["emit"]
        B = tokens.shape[0]
        ps = pages["k"].shape[3]
        H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

        x = _embed(outer, cfg, tokens)                     # [B,1,d]
        # page/slot the incoming token lands in; frozen rows -> scratch 0
        pidx = jnp.where(emit, bt[jnp.arange(B), n // ps], 0)
        slot = jnp.where(emit, n % ps, 0)
        n_incl = n + 1                                     # incl. this token
        posv = n.astype(jnp.float32)[:, None]              # [B,1]
        d_rot = int(dh * cfg.rope_pct) // 2 * 2
        sin, cos = L.rope_sincos(posv, d_rot, cfg.rope_theta)
        aux0 = jnp.zeros((), jnp.float32)

        def body(carry, xs):
            x, _ = carry
            layer_p, kp, vp = xs
            h = L.norm_apply(layer_p["ln1"], x, kind=cfg.norm)
            q = L.dense(h, layer_p["attn"]["wq"]).reshape(B, 1, H, dh)
            k = L.dense(h, layer_p["attn"]["wk"]).reshape(B, 1, K, dh)
            v = L.dense(h, layer_p["attn"]["wv"]).reshape(B, 1, K, dh)
            if cfg.qk_norm:
                q = L.rmsnorm(q, layer_p["attn"]["q_norm"]["scale"])
                k = L.rmsnorm(k, layer_p["attn"]["k_norm"]["scale"])
            q = L.apply_rope(q, sin, cos, cfg.rope_pct)
            k = L.apply_rope(k, sin, cos, cfg.rope_pct)
            kp = kp.at[pidx, :, slot].set(k[:, 0])       # [B, K, dh]
            vp = vp.at[pidx, :, slot].set(v[:, 0])
            with jax.named_scope("attention"):
                o = paged_decode_attention(q, kp, vp, bt, n_incl,
                                           window=cfg.window,
                                           use_kernel=use_kernel,
                                           interpret=interpret)
            a = L.dense(o.reshape(B, 1, H * dh), layer_p["attn"]["wo"])
            x = x + a
            h = L.norm_apply(layer_p["ln2"], x, kind=cfg.norm)
            if cfg.moe is not None:
                y, _ = moe_ffn(layer_p["moe"], h, cfg.moe)
                x = x + y
            elif cfg.glu:
                x = x + L.glu_mlp(layer_p["mlp"], h, cfg.act)
            else:
                x = x + L.mlp(layer_p["mlp"], h, cfg.act)
            return (x, aux0), (kp, vp)

        xs = (params["stacks"]["blocks"], pages["k"], pages["v"])
        (x, _), (k_new, v_new) = jax.lax.scan(body, (x, aux0), xs)
        h = L.norm_apply(outer["final_norm"], x, kind=cfg.norm)
        logits = _logits(outer, cfg, h)[:, 0]
        return logits, {"k": k_new, "v": v_new}

    return decode


def init_page_pool(cfg: LMConfig, num_pages: int, page_size: int) -> dict:
    """Zeroed shared KV page pool (page 0 is the engine's scratch page)."""
    assert cfg.mla is None, "paged serving supports GQA caches only"
    shape = (cfg.n_layers, num_pages, cfg.n_kv_heads, page_size,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def make_prefill_step(cfg: LMConfig):
    """prefill_step(params, batch) -> (last_logits, cache). Computes the
    full-sequence forward and materializes the KV cache for decoding."""
    def prefill_step(params, batch):
        outer = params["outer"]
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = _embed(outer, cfg, tokens)
        if cfg.n_prefix_tokens:
            x = jnp.concatenate([batch["prefix_embed"].astype(x.dtype), x],
                                axis=1)
            S = S + cfg.n_prefix_tokens
        pos = jnp.arange(S, dtype=jnp.int32)
        prefix_len = None
        if cfg.prefix_lm:
            prefix_len = batch["prefix_len"].astype(jnp.int32)
        W = cache_window(cfg, S)
        body_train = make_block_body(cfg)

        def body(carry, layer_p):
            x, aux = carry
            ctx = ({}, {"pos": pos.astype(jnp.float32)}
                   if prefix_len is None else
                   {"pos": pos.astype(jnp.float32),
                    "prefix": prefix_len.astype(jnp.float32)})
            (x2, aux2) = body_train(layer_p, ctx, (x, aux), 0)
            # recompute this layer's KV for the cache (last W positions)
            h = L.norm_apply(layer_p["ln1"], x, kind=cfg.norm)
            if cfg.mla is not None:
                m = cfg.mla
                ckv = L.rmsnorm(L.dense(h, layer_p["attn"]["w_dkv"]),
                                layer_p["attn"]["kv_ln"]["scale"])
                kr = L.dense(h, layer_p["attn"]["w_kr"]).reshape(
                    B, S, 1, m.d_rope)
                sin, cos = L.rope_sincos(pos.astype(jnp.float32), m.d_rope,
                                         cfg.rope_theta)
                kr = L.apply_rope(kr, sin, cos).reshape(B, S, m.d_rope)
                cache_slice = (ckv[:, S - W:], kr[:, S - W:])
            else:
                K, dh = cfg.n_kv_heads, cfg.head_dim
                k = L.dense(h, layer_p["attn"]["wk"]).reshape(B, S, K, dh)
                if cfg.qk_norm:
                    k = L.rmsnorm(k, layer_p["attn"]["k_norm"]["scale"])
                d_rot = int(dh * cfg.rope_pct) // 2 * 2
                sin, cos = L.rope_sincos(pos.astype(jnp.float32), d_rot,
                                         cfg.rope_theta)
                k = L.apply_rope(k, sin, cos, cfg.rope_pct)
                v = L.dense(h, layer_p["attn"]["wv"]).reshape(B, S, K, dh)
                cache_slice = (k[:, S - W:], v[:, S - W:])
            return (x2, aux2), cache_slice

        (x, _), cache_stk = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            params["stacks"]["blocks"])
        h = L.norm_apply(outer["final_norm"], x[:, -1:], kind=cfg.norm)
        logits = _logits(outer, cfg, h)[:, 0]
        pos_tab = pos[S - W:]
        if cfg.mla is not None:
            cache = {"ckv": cache_stk[0], "kr": cache_stk[1],
                     "pos": pos_tab, "cur": jnp.asarray(S, jnp.int32)}
        else:
            cache = {"k": cache_stk[0], "v": cache_stk[1],
                     "pos": pos_tab, "cur": jnp.asarray(S, jnp.int32)}
        return logits, cache

    return prefill_step
