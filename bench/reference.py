"""The plain reference for the dense decoder configurations (h2o-danube
and Qwen3 families): the model and AdaLomo written out directly in
``jax.numpy``, in float32 with every matmul at ``Precision.HIGHEST``.  It
imports nothing of the program and takes nothing the program made: it
makes its weights again from the seed (``bench.weights``) and its batches
from the traffic generator.

It runs one layer at a time (weights kept in the served type, upcast per
layer; attention per (row, key-value head) and the loss per block of
tokens under ``jax.checkpoint``).  Training keeps each layer's state
between passes (its weights, moments and input) on one of the cell's
devices and runs the layer there: the outer leaves and the loss on the
first, the layers round the others (all on the one device of a one-chip
cell).  So a model fits beside nothing else on the chips its cell holds,
and where it is spread the numbers are those of a single-device run.

``quant`` switches every matmul to a lower precision: the control
(``fp8``) is this reference computed as float8 training computes it
(operands in e4m3, result cotangents in e5m2, one scale per tensor), the
step below the configuration's bfloat16.

Model, as published for Llama/Mistral-style decoders: RMSNorm (weight =
1 + stored value, eps from the configuration), rotary embeddings on
halves of each head (theta from the configuration), grouped-query
attention, causal, inside a sliding window and inside its own document,
SwiGLU MLP, untied head, mean next-token cross-entropy over the tokens
that carry a label.

Qwen3 (``"qk_norm": true``), as published for ``Qwen3ForCausalLM``: the
same layer, with q and k each put through RMSNorm per head, over
``head_dim`` (weight = 1 + stored value, the configuration's eps), after
their projections and before the rotary embedding:
q = RoPE(RMSNorm_q(h W_q)), k = RoPE(RMSNorm_k(h W_k)), v = h W_v.

AdaLomo, as in the paper's Algorithm 1: r, c as EMAs of the row and
column sums of g² (+1e-30), v = r cᵀ / Σr, bias-corrected; u = g /
(√v̂ + ε); û = u / max(1, RMS(u)/clip) · max(ε₂, RMS(θ)); θ ← θ(1 - lr·wd)
- lr·û, rounded to the parameter's type.  Tensors with a side under 16,
and 1-D ones, keep an unfactored v.
"""
from __future__ import annotations

from functools import partial

import numpy as np

from bench import weights as W

HIGHEST = "highest"
# the loss takes its logits at most this many at a time (512 MiB in
# float32): a whole row where that fits (danube's 4096 x 32000), halves of
# rows where the vocabulary is wider (Qwen3's 151936: 512 tokens)
LOGITS_BLOCK = 2 ** 27


def _jnp():
    import jax.numpy as jnp
    return jnp


def _scaled(a, dtype):
    """a rounded to a float8 type, with one scale per tensor that maps its
    largest magnitude to the type's largest value."""
    jnp = _jnp()
    s = jnp.max(jnp.abs(a)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(dtype).astype(jnp.float32) * s


def dot(spec: str, a, b):
    """An einsum in float32 at the highest precision."""
    jnp = _jnp()
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def fp8_dot(spec: str, a, b):
    """The einsum as float8 training computes it: both operands in e4m3,
    the cotangent of the result in e5m2, each with one scale per tensor,
    products summed in float32."""
    import jax
    jnp = _jnp()
    e4, e5 = jnp.float8_e4m3fn, jnp.float8_e5m2

    @jax.custom_vjp
    def f(a, b):
        return dot(spec, _scaled(a, e4), _scaled(b, e4))

    def fwd(a, b):
        qa, qb = _scaled(a, e4), _scaled(b, e4)
        return dot(spec, qa, qb), (qa, qb)

    def bwd(res, dy):
        _, vjp = jax.vjp(partial(dot, spec), *res)
        return vjp(_scaled(dy, e5))

    f.defvjp(fwd, bwd)
    return f(a, b)


DOTS = {"none": dot, "fp8": fp8_dot}


def mm(a, b, quant):
    return quant("...i,ij->...j", a, b)


def rmsnorm(x, stored, eps):
    jnp = _jnp()
    import jax
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + stored)


def rope(x, pos, theta):
    """x [B,S,n,dh], pos [B,S]: rotate the two halves of each head."""
    jnp = _jnp()
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [B,S,half]
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, pos, seg, window, quant):
    """q [B,S,H,dh], k/v [B,S,K,dh] -> [B,S,H,dh]."""
    import jax
    jnp = _jnp()
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    qs = q.reshape(B, S, K, G, dh).transpose(0, 2, 3, 1, 4)   # [B,K,G,S,dh]
    ks = k.transpose(0, 2, 1, 3)                              # [B,K,S,dh]
    vs = v.transpose(0, 2, 1, 3)
    p = jnp.broadcast_to(pos[:, None], (B, K, S))
    s = jnp.broadcast_to(seg[:, None], (B, K, S))

    def one(args):
        qi, ki, vi, pi, si = args
        logits = quant("gqd,kd->gqk", qi, ki) * dh ** -0.5
        mask = (pi[:, None] >= pi[None, :]) & (si[:, None] == si[None, :])
        if window:
            mask = mask & (pi[:, None] - pi[None, :] < window)
        logits = jnp.where(mask[None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return quant("gqk,kd->gqd", probs, vi)

    flat = lambda a: a.reshape((B * K,) + a.shape[2:])
    out = jax.lax.map(jax.checkpoint(one),
                      (flat(qs), flat(ks), flat(vs), flat(p), flat(s)))
    out = out.reshape(B, K, G, S, dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, dh)


def block(cfg, quant, w, x, pos, seg):
    """One decoder layer; w holds the stored weights of the layer."""
    import jax
    jnp = _jnp()
    m = W.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = {k: a.astype(jnp.float32) for k, a in w.items()}
    B, S, _ = x.shape
    h = rmsnorm(x, f32["ln1.scale"], eps)
    q = mm(h, f32["attn.wq"], quant).reshape(B, S, m["H"], m["dh"])
    k = mm(h, f32["attn.wk"], quant).reshape(B, S, m["K"], m["dh"])
    v = mm(h, f32["attn.wv"], quant).reshape(B, S, m["K"], m["dh"])
    if cfg.get("qk_norm"):
        q = rmsnorm(q, f32["attn.q_norm.scale"], eps)
        k = rmsnorm(k, f32["attn.k_norm.scale"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    o = attention(q, k, v, pos, seg, cfg.get("sliding_window"), quant)
    x = x + mm(o.reshape(B, S, -1), f32["attn.wo"], quant)
    h = rmsnorm(x, f32["ln2.scale"], eps)
    g = mm(h, f32["mlp.w_gate"], quant)
    u = mm(h, f32["mlp.w_up"], quant)
    return x + mm(jax.nn.silu(g) * u, f32["mlp.w_down"], quant)


def loss_sums(cfg, quant, final_scale, head, x, labels):
    """(Σ cross-entropy over labelled tokens, their count), a block of
    tokens at a time: a row, halved until its logits are at most
    ``LOGITS_BLOCK``."""
    import jax
    jnp = _jnp()
    n = x.shape[1]
    while n * head.shape[1] > LOGITS_BLOCK and n % 2 == 0:
        n //= 2
    x = x.reshape(-1, n, x.shape[-1])
    labels = labels.reshape(-1, n)

    def row(args):
        xr, lr = args
        h = rmsnorm(xr, final_scale.astype(jnp.float32), cfg["rms_norm_eps"])
        logits = mm(h, head.astype(jnp.float32), quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lr, 0)[:, None], -1)
        return jnp.sum(jnp.where(lr >= 0, lse - tgt[:, 0], 0.0))

    sums = jax.lax.map(jax.checkpoint(row), (x, labels))
    return jnp.sum(sums), jnp.sum(labels >= 0)


def logits_at(cfg, quant, final_scale, head, x):
    jnp = _jnp()
    h = rmsnorm(x, final_scale.astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(h, head.astype(jnp.float32), quant)


# --------------------------------------------------------------------------
# AdaLomo
# --------------------------------------------------------------------------

def adalomo_init(shape) -> dict:
    jnp = _jnp()
    if len(shape) == 2 and min(shape) >= 16:
        return {"r": jnp.zeros(shape[0], jnp.float32),
                "c": jnp.zeros(shape[1], jnp.float32)}
    return {"v": jnp.zeros(shape, jnp.float32)}


def adalomo(theta, g, st, t, hp):
    """One AdaLomo step of one tensor; returns (θ', state')."""
    jnp = _jnp()
    b = jnp.float32(hp["beta"])
    g2 = g * g + 1e-30
    if "r" in st:
        r = b * st["r"] + (1 - b) * jnp.sum(g2, axis=1)
        c = b * st["c"] + (1 - b) * jnp.sum(g2, axis=0)
        v = r[:, None] * c[None, :] / jnp.sum(r)
        st = {"r": r, "c": c}
    else:
        v = b * st["v"] + (1 - b) * g2
        st = {"v": v}
    vhat = v / (1 - b ** t)
    u = g / (jnp.sqrt(vhat) + hp["eps"])
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)) / hp["clip"])
    th = theta.astype(jnp.float32)
    u = u * jnp.maximum(hp["eps2"], jnp.sqrt(jnp.mean(th * th)))
    new = th * (1 - hp["lr"] * hp["weight_decay"]) - hp["lr"] * u
    return new.astype(theta.dtype), st


# --------------------------------------------------------------------------
# Training: three steps, layer by layer
# --------------------------------------------------------------------------

def _embed_grad(tok, dx, vocab):
    jnp = _jnp()
    return jnp.zeros((vocab, dx.shape[-1]), dx.dtype).at[tok].add(dx)


def _norm(a):
    jnp = _jnp()
    a = a.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(a * a))


class TrainReference:
    """Runs the reference's training steps on batches from the traffic
    generator and reads, per tensor (each layer's slice apart): the first
    step's gradient norm and the parameters' change after the steps."""

    def __init__(self, cfg: dict, opt: dict, quant: str = "none",
                 devices=None):
        import jax
        self.cfg = W.Static(cfg)
        self.devices = list(devices or jax.devices()[:1])
        self.hp = {k: float(opt[k]) for k in
                   ("lr", "beta", "clip", "weight_decay", "eps", "eps2")}
        self.q = DOTS[quant]
        self.fwd = jax.jit(partial(block, self.cfg, self.q))
        self.bwd = jax.jit(self._layer_grads)
        self.epi = jax.jit(self._epilogue_grads)
        self.upd = jax.jit(adalomo)
        self.embed_grad = jax.jit(_embed_grad, static_argnums=2)

    def _layer_grads(self, w, x, pos, seg, dy):
        """(weight gradients in float32, input gradient) of one layer."""
        import jax
        w32 = {k: a.astype(_jnp().float32) for k, a in w.items()}
        _, vjp = jax.vjp(lambda w_, x_: block(self.cfg, self.q, w_, x_, pos,
                                              seg), w32, x)
        return vjp(dy)

    def _epilogue_grads(self, final, head, x, labels):
        """(loss, grad final norm, grad head, grad x)."""
        import jax
        jnp = _jnp()

        def f(final_, head_, x_):
            s, n = loss_sums(self.cfg, self.q, final_, head_, x_, labels)
            return s / jnp.maximum(n, 1)

        loss, vjp = jax.vjp(f, final.astype(jnp.float32),
                            head.astype(jnp.float32), x)
        return (loss,) + vjp(jnp.ones_like(loss))

    def device(self, layer=None):
        """Where a layer's state lives and its passes run.  The outer
        leaves (``layer=None``) and the loss live on the first device; on
        more than one, the layers go round the others, since the loss's
        vocabulary-wide temporaries leave the first no room for layers."""
        if layer is None:
            return self.devices[0]
        spread = self.devices[1:] or self.devices
        return spread[layer % len(spread)]

    def made_on(self, dev, make, *args):
        """``make(cfg, seed, ...)`` of ``bench.weights``, made on ``dev``
        and committed to it."""
        import jax
        with jax.default_device(dev):
            return jax.device_put(make(self.cfg, *args), dev)

    def update(self, theta, state, grad, t, norms, layer):
        """AdaLomo step ``t`` of each tensor of ``theta`` that ``grad``
        holds, in place; at step 1 keeps each gradient's norm in ``norms``
        under (path, layer).  Returns once the step is done, so that a
        gradient the caller drops is freed before the next pass: at
        Qwen3's vocabulary the head's and the embedding's are 3.1 GB each
        in float32."""
        import jax
        jnp = _jnp()
        for p, g in grad.items():
            if t == 1:
                norms[(p, layer)] = _norm(g)
            theta[p], state[p] = self.upd(theta[p], g.astype(jnp.float32),
                                          state[p], jnp.float32(t), self.hp)
        jax.block_until_ready(theta)

    def run(self, seed: int, batches: list) -> dict:
        """Reference readings over ``len(batches)`` steps from the seed's
        weights: losses, first gradient norms, change norms."""
        import jax
        jnp = _jnp()
        cfg = self.cfg
        L, V = W.dims(cfg)["L"], W.dims(cfg)["V"]
        at = self.device
        outer = {p: self.made_on(at(), W.outer_leaf, seed, p)
                 for p, _ in W.OUTER}
        layers = [self.made_on(at(l), W.layer, seed, l) for l in range(L)]
        o_state = {p: adalomo_init(a.shape) for p, a in outer.items()}
        l_state = [jax.device_put({p: adalomo_init(a.shape)
                                   for p, a in lw.items()}, at(l))
                   for l, lw in enumerate(layers)]
        losses, first_grad = [], {}
        for t, batch in enumerate(batches, start=1):
            tok = jnp.asarray(batch["tokens"])
            lab = jnp.asarray(batch["labels"])
            B, S = tok.shape
            pos = np.asarray(batch.get(
                "positions", np.broadcast_to(np.arange(S), (B, S))))
            seg = np.asarray(batch.get("segment_ids",
                                       np.ones((B, S), np.int32)))
            rows = {d: jax.device_put((pos, seg), d) for d in self.devices}
            x = outer["tok_embed"][tok].astype(jnp.float32)
            xs = []
            for l, lw in enumerate(layers):
                x = jax.device_put(x, at(l))
                xs.append(x)
                x = self.fwd(lw, x, *rows[at(l)])
            loss, g_final, g_head, dx = self.epi(
                outer["final_norm.scale"], outer["head"],
                jax.device_put(x, at()), lab)
            losses.append(float(loss))
            grads = {}
            # the head's update needs nothing of the layers' pass: made
            # first, it frees the head's float32 gradient for it
            self.update(outer, o_state, {"final_norm.scale": g_final,
                                         "head": g_head}, t, grads, None)
            del g_final, g_head
            for l in reversed(range(L)):
                g_w, dx = self.bwd(layers[l], xs[l], *rows[at(l)],
                                   jax.device_put(dx, at(l)))
                xs[l] = None
                self.update(layers[l], l_state[l], g_w, t, grads, l)
                del g_w
            g_embed = self.embed_grad(tok, jax.device_put(dx, at()), V)
            self.update(outer, o_state, {"tok_embed": g_embed}, t, grads,
                        None)
            del xs, dx, g_embed
            if t == 1:
                first_grad = {k: float(v) for k, v in grads.items()}
        change = {}
        diff = jax.jit(lambda a, b: _norm(a.astype(jnp.float32)
                                          - b.astype(jnp.float32)))
        for p, a in outer.items():
            change[(p, None)] = float(diff(
                a, self.made_on(at(), W.outer_leaf, seed, p)))
        for l in range(L):
            for p, a in layers[l].items():
                change[(p, l)] = float(diff(
                    a, self.made_on(at(l), W.layer_leaf, seed, p, l)))
        return {"losses": losses, "grad": first_grad, "change": change}


# --------------------------------------------------------------------------
# Serving: logits over whole sequences, layer by layer
# --------------------------------------------------------------------------

class LogitsReference:
    """Logits of one sequence at every position, from the seed's weights.
    Sequences are padded to a power of two (causal: the padding is never
    seen by real positions) so few shapes compile."""

    def __init__(self, cfg: dict, seed: int, quant: str = "none"):
        import jax
        self.cfg = W.Static(cfg)
        q = DOTS[quant]
        self.fwd = jax.jit(partial(block, self.cfg, q))
        self.head = jax.jit(partial(logits_at, self.cfg, q))
        L = W.dims(cfg)["L"]
        self.outer = {p: W.outer_leaf(cfg, seed, p) for p, _ in W.OUTER}
        self.layers = [W.layer(cfg, seed, l) for l in range(L)]

    def logits(self, tokens: list[int]) -> np.ndarray:
        jnp = _jnp()
        n = len(tokens)
        S = 1 << max(4, (n - 1).bit_length())
        tok = np.zeros((1, S), np.int32)
        tok[0, :n] = tokens
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        seg = jnp.ones((1, S), jnp.int32)
        x = self.outer["tok_embed"][jnp.asarray(tok)].astype(jnp.float32)
        for lw in self.layers:
            x = self.fwd(lw, x, pos, seg)
        out = self.head(self.outer["final_norm.scale"], self.outer["head"],
                        x[0])
        return np.asarray(out[:n])
