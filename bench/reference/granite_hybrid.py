"""A plain float32 reference of Granite 4.0-H (`granitemoehybrid`): forward,
loss and gradients in `jax.numpy`, at highest matmul precision, importing
nothing of the program.

It follows the published config's layer equations
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json):

  x = embed[tokens] * embedding_multiplier
  each layer:  x += residual_multiplier * mixer(rmsnorm(x))
               x += residual_multiplier * swiglu(rmsnorm(x))
  logits = rmsnorm(x) @ embedᵀ / logits_scaling       (tied embeddings)

  mamba mixer:  [z | xBC | dt] = x @ in_proj;  xBC = silu(causal_conv(xBC) + b)
                dt = softplus(dt + dt_bias);  A = -exp(A_log)
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ;   y_t = h_t C_t + D x_t
                out = (rmsnorm(y * silu(z)) * w) @ out_proj
  attention:    GQA, no positional encoding, softmax scale attention_multiplier

The state-space recurrence is computed token by token, as published, and
not in chunks: the program's chunked (SSD) algorithm is what it checks.

Departures from the source:
  * packed documents: the recurrent state and the conv's history are zeroed
    at the first token of each document, and attention stays inside its
    document, as the Mamba-2 kernels' `seq_idx` and a packed-attention mask
    do in training;
  * the vocabulary may be a slice of the published rows (the benchmark
    holds one chip's share): the ids, logits and loss are over the slice;
  * weights are random from a seed: uniform ±1/sqrt(fan_in) (the embedding's
    fan-in is the hidden size, the conv's its width), A_log = log U(1, 16),
    dt_bias = softplus⁻¹ of a log-uniform dt in [1e-3, 1e-1], D = 1, norms 1;
  * the memory of a long sequence is bounded by computing attention in
    query blocks and the recurrence and the loss in blocks of tokens, each
    recomputed in the backward pass; the numbers are those of the plain
    formulas.

For the benchmark's readings, `Variant` puts the recurrence's state and
decays in bfloat16 (the control, one step below float32) or drops the state
at every `reset_every` tokens (the planted fault: no carry between chunks).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512        # attention query block
T_BLOCK = 256        # recurrence and loss block


@dataclasses.dataclass(frozen=True)
class Variant:
    state_dtype: str = "float32"
    reset_every: int = 0


SOUND = Variant()


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    hq: int
    hk: int
    hd: int
    mh: int           # mamba heads
    mp: int           # mamba head dim
    n: int            # d_state
    g: int            # groups
    k: int            # conv width
    vocab: int
    types: tuple
    eps: float
    em: float
    rm: float
    ls: float
    scale: float

    @property
    def di(self) -> int:
        return self.mh * self.mp

    @property
    def conv_dim(self) -> int:
        return self.di + 2 * self.g * self.n


def dims(cfg: dict) -> Dims:
    for key, want in (("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("position_embedding_type", "nope"),
                      ("tie_word_embeddings", True), ("hidden_act", "silu")):
        if cfg[key] != want:
            raise ValueError(f"{key} = {cfg[key]!r}: the reference holds {want!r} only")
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(d=d, f=cfg["shared_intermediate_size"], hq=hq,
                hk=cfg["num_key_value_heads"], hd=d // hq, mh=cfg["mamba_n_heads"],
                mp=cfg["mamba_d_head"], n=cfg["mamba_d_state"], g=cfg["mamba_n_groups"],
                k=cfg["mamba_d_conv"], vocab=cfg["vocab_size"],
                types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
                eps=cfg["rms_norm_eps"], em=cfg["embedding_multiplier"],
                rm=cfg["residual_multiplier"], ls=cfg["logits_scaling"],
                scale=cfg["attention_multiplier"])


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

def _u(key, shape, fan_in):
    b = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, F32, -b, b)


def init(key, cfg: dict) -> dict:
    """The weights, from a key (see the departures above)."""
    m = dims(cfg)
    ke, *kl = jax.random.split(key, 1 + len(m.types))
    layers = []
    for k, t in zip(kl, m.types):
        ks = jax.random.split(k, 9)
        ffn = dict(ln2=jnp.ones((m.d,), F32), wg=_u(ks[0], (m.d, m.f), m.d),
                   wu=_u(ks[1], (m.d, m.f), m.d), wd=_u(ks[2], (m.f, m.d), m.f))
        if t == "mamba":
            dt = jnp.exp(jax.random.uniform(ks[3], (m.mh,), F32, math.log(1e-3),
                                            math.log(1e-1)))
            mix = dict(in_proj=_u(ks[4], (m.d, m.di + m.conv_dim + m.mh), m.d),
                       conv_w=_u(ks[5], (m.k, m.conv_dim), m.k),
                       conv_b=_u(ks[6], (m.conv_dim,), m.k),
                       dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                       A_log=jnp.log(jax.random.uniform(ks[7], (m.mh,), F32, 1.0, 16.0)),
                       D=jnp.ones((m.mh,), F32), norm=jnp.ones((m.di,), F32),
                       out_proj=_u(ks[8], (m.di, m.d), m.di))
        elif t == "attention":
            mix = dict(wq=_u(ks[4], (m.d, m.hq, m.hd), m.d),
                       wk=_u(ks[5], (m.d, m.hk, m.hd), m.d),
                       wv=_u(ks[6], (m.d, m.hk, m.hd), m.d),
                       wo=_u(ks[7], (m.hq, m.hd, m.d), m.hq * m.hd))
        else:
            raise ValueError(t)
        layers.append(dict(ln1=jnp.ones((m.d,), F32), **mix, **ffn))
    return dict(embedding=_u(ke, (m.vocab, m.d), m.d), final_norm=jnp.ones((m.d,), F32),
                layers=layers)


# --------------------------------------------------------------------------- #
# layers
# --------------------------------------------------------------------------- #

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def starts(seg):
    """True at the first token of each document. seg: (b, s)."""
    return jnp.concatenate([jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], 1)


def causal_conv(x, w, b, seg):
    """y_t = b + sum_k w[K-1-k] x_{t-k}, over the taps still in x_t's document."""
    kw, s = w.shape[0], x.shape[1]
    y = jnp.broadcast_to(b, x.shape)
    for lag in range(kw):
        prev = jnp.pad(x, ((0, 0), (lag, 0), (0, 0)))[:, :s]
        same = seg == jnp.pad(seg, ((0, 0), (lag, 0)), constant_values=-1)[:, :s]
        y = y + jnp.where(same[..., None], prev, 0.0) * w[kw - 1 - lag]
    return y


def recurrence(x, dt, A, B, C, reset, v: Variant = SOUND):
    """The state-space recurrence token by token.  x: (b, s, H, P);
    dt: (b, s, H); B, C: (b, s, G, N); reset: (b, s) bool.  Returns
    y_t = h_t C_t: (b, s, H, P)."""
    sd = jnp.dtype(v.state_dtype)
    (nb, s, nh, np_), n = x.shape, B.shape[-1]
    if v.reset_every:
        reset = reset | (jnp.arange(s) % v.reset_every == 0)[None]
    r = nh // B.shape[2]
    Bh = jnp.repeat(B, r, axis=2)          # head i reads group i // (H / G)
    Ch = jnp.repeat(C, r, axis=2)

    def step(h, inp):
        x_t, dt_t, b_t, c_t, z_t = inp
        a = jnp.where(z_t[:, None], 0.0, jnp.exp(dt_t * A).astype(sd).astype(F32))
        h = a[..., None, None] * h.astype(F32) + (dt_t[..., None, None] * x_t[..., None]
                                                  * b_t[:, :, None, :])
        h = h.astype(sd)
        return h, jnp.einsum("bhpn,bhn->bhp", h.astype(F32), c_t)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    tb = min(T_BLOCK, s)
    assert s % tb == 0
    split = lambda a: jnp.moveaxis(a, 1, 0).reshape(s // tb, tb, *a.shape[:1], *a.shape[2:])
    h0 = jnp.zeros((nb, nh, np_, n), sd)
    _, ys = jax.lax.scan(block, h0, tuple(split(a) for a in (x, dt, Bh, Ch, reset)))
    return jnp.moveaxis(ys.reshape(s, *ys.shape[2:]), 0, 1)


def _ssm_inputs(x, p, seg, m: Dims):
    """The gate z and the recurrence's inputs (x, dt, A, B, C) of a mixer."""
    b, s, _ = x.shape
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [m.di, m.di + m.conv_dim], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"], seg))
    xs, B, C = jnp.split(xbc, [m.di, m.di + m.g * m.n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    return z, (xs.reshape(b, s, m.mh, m.mp), dt, A, B.reshape(b, s, m.g, m.n),
               C.reshape(b, s, m.g, m.n))


def mamba(x, p, seg, m: Dims, v: Variant):
    b, s, _ = x.shape
    z, (xs, dt, A, B, C) = _ssm_inputs(x, p, seg, m)
    y = recurrence(xs, dt, A, B, C, starts(seg), v)
    y = (y + p["D"][:, None] * xs).reshape(b, s, m.di)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"], m.eps)
    return y @ p["out_proj"]


def attention(x, p, seg, m: Dims):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), m.hq // m.hk, axis=2)
    v = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), m.hq // m.hk, axis=2)
    qb = min(Q_BLOCK, s)
    assert s % qb == 0
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        si = jax.lax.dynamic_slice_in_dim(seg, i * qb, qb, axis=1)
        pi = i * qb + jnp.arange(qb)
        ok = (pos[None, :] <= pi[:, None])[None] & (si[:, :, None] == seg[:, None, :])
        sc = jnp.einsum("bqhk,bthk->bhqt", qi, k) * m.scale
        sc = jnp.where(ok[:, None], sc, -jnp.inf)
        return None, jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(sc, -1), v)

    _, out = jax.lax.scan(block, None, jnp.arange(s // qb))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, m.hq, m.hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def _layer(x, p, t: str, seg, m: Dims, v: Variant):
    h = rmsnorm(x, p["ln1"], m.eps)
    h = mamba(h, p, seg, m, v) if t == "mamba" else attention(h, p, seg, m)
    x = x + m.rm * h
    return x + m.rm * swiglu(rmsnorm(x, p["ln2"], m.eps), p)


def _embed(params, batch, m: Dims):
    tokens = batch["tokens"]
    seg = batch.get("segment_ids", jnp.zeros(tokens.shape, jnp.int32))
    return params["embedding"][tokens] * m.em, seg


def hidden(params, batch, cfg: dict, v: Variant = SOUND):
    """The final norm's output: (b, s, d)."""
    m = dims(cfg)
    x, seg = _embed(params, batch, m)
    for t, p in zip(m.types, params["layers"]):
        x = jax.checkpoint(partial(_layer, t=t, seg=seg, m=m, v=v))(x, p)
    return rmsnorm(x, params["final_norm"], m.eps)


def ssd_inputs(params, batch, cfg: dict):
    """The first Mamba layer's recurrence inputs (x, dt, A, B, C) for a
    batch, and its resets (the first token of each document)."""
    m = dims(cfg)
    x, seg = _embed(params, batch, m)
    for t, p in zip(m.types, params["layers"]):
        if t == "mamba":
            return _ssm_inputs(rmsnorm(x, p["ln1"], m.eps), p, seg, m)[1], starts(seg)
        x = _layer(x, p, t, seg, m, SOUND)
    raise ValueError("the configuration has no mamba layer")


def logits(params, batch, cfg: dict, v: Variant = SOUND):
    return hidden(params, batch, cfg, v) @ params["embedding"].T / dims(cfg).ls


def loss(params, batch, cfg: dict, v: Variant = SOUND):
    """Mean cross-entropy over the labels that are not -100."""
    m = dims(cfg)
    x = hidden(params, batch, cfg, v)
    lab = batch["labels"]
    s = lab.shape[1]
    tb = min(T_BLOCK, s)
    assert s % tb == 0

    @jax.checkpoint
    def block(acc, i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, axis=1)
        li = jax.lax.dynamic_slice_in_dim(lab, i * tb, tb, axis=1)
        lg = xi @ params["embedding"].T / m.ls
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, jnp.maximum(li, 0)[..., None], -1)[..., 0]
        return acc + jnp.sum(jnp.where(li >= 0, nll, 0.0)), None

    total, _ = jax.lax.scan(block, jnp.zeros((), F32), jnp.arange(s // tb))
    return total / jnp.maximum(jnp.sum(lab >= 0), 1)


def loss_and_grads(cfg: dict, v: Variant = SOUND):
    """A jitted (params, batch) -> (loss, grads) at highest precision."""
    f = jax.jit(jax.value_and_grad(partial(loss, cfg=cfg, v=v)))

    def run(params, batch):
        with jax.default_matmul_precision("highest"):
            return f(params, batch)
    return run


def lattice(g):
    """Q15.16: round to the nearest multiple of 2^-16 (ties to even),
    saturating at the 32-bit range."""
    return jnp.round(jnp.clip(g * 65536.0, -2.0 ** 31, 2.0 ** 31 - 1)) / 65536.0


def _clipped(grads, clip: float, on_lattice: bool):
    """Gradients put on the Q15.16 lattice (where `on_lattice`: QAT's
    gradient memory) and then clipped to a global norm, in that order."""
    q = jax.tree.map(lattice, grads) if on_lattice else grads
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(q)))
    scale = jnp.minimum(1.0, clip / (norm + 1e-12))
    return jax.tree.map(lambda x: x * scale, q)


@partial(jax.jit, static_argnames=("b1", "clip", "on_lattice"))
def first_moment(grads, b1: float, clip: float, on_lattice: bool):
    """Adam's first moment after one step from zero, and the share of
    gradient elements the lattice zeroes (or would zero)."""
    zeroed = sum(jnp.sum((lattice(g) == 0) & (g != 0)) for g in jax.tree.leaves(grads))
    size = sum(g.size for g in jax.tree.leaves(grads))
    return (jax.tree.map(lambda g: (1.0 - b1) * g, _clipped(grads, clip, on_lattice)),
            zeroed / size)


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "clip", "on_lattice"))
def first_change(params, grads, lr: float, b1: float, b2: float, eps: float, clip: float,
                 on_lattice: bool):
    """The parameters' change in Adam's first step from zero moments (the
    new weights put back on the lattice where `on_lattice`)."""
    def leaf(p, g):
        m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
        new = p - lr * (m / (1.0 - b1)) / (jnp.sqrt(v / (1.0 - b2)) + eps)
        return (lattice(new) if on_lattice else new) - p
    return jax.tree.map(leaf, params, _clipped(grads, clip, on_lattice))
