"""Plain reference of the served model, independent of the program.

A DeepSeek-V2 decoder (multi-head latent attention, dense first layers,
routed and shared experts) in straightforward ``jax.numpy``, computed in
float32 at ``highest`` matmul precision, from nothing but the
configuration file's keys and the run's seed. It imports nothing of the
program and takes nothing the program made: it draws the weights itself,
one layer at a time, with the key schedule the program's weight init
follows (``weight_key``, then ``split`` into embedding, stack and head
keys, one key per layer, and per leaf as listed below), so both sides hold
the same weights, in the type the configuration serves them in. Attention
is the materialised form of the DeepSeek-V2 paper (per-head keys and
values from the compressed latent), not the absorbed form the program
decodes with.

It carries the departures from the source configuration that the
configuration file lists under ``reduced``, because the program makes
them: RoPE with ``rope_theta`` alone (no YaRN scaling), a plain top-k over
the softmax of all experts, top-k weights renormalised to sum to one, no
routed scaling. Norm weights are ones, as the program initialises them.

The controls are the same computation a precision below the bfloat16 the
configuration states: ``mode="int8"`` (symmetric, rounded to 127 levels)
and ``mode="fp8"`` (e4m3) round every weight matmul's operands, with a
scale per output channel of the weight and per token of the activation,
and the embedding per row. The router and the attention products stay
float32.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BATCH = 8          # sequences per reference pass (one compiled shape)
LEN_BUCKET = 512   # sequence lengths are padded to a multiple of this
POS_BLOCK = 256    # compared positions per logits program (one shape)
CONTROLS = ("int8", "fp8")


def weight_key(seed: int):
    """The PRNG key the program's weight init and this reference draw
    from; folds the seed's high bits in, so seeds past 2**32 stay apart."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


class Dims(NamedTuple):
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    rope: int
    nope: int
    v: int
    d_dense: int
    d_expert: int
    experts: int
    shared: int
    top_k: int
    first_dense: int
    layers: int
    vocab: int
    eps: float
    theta: float
    dtype: str        # the weights' type as served


def dims(conf: dict) -> Dims:
    return Dims(conf["hidden_size"], conf["num_attention_heads"],
                conf["q_lora_rank"] or 0, conf["kv_lora_rank"],
                conf["qk_rope_head_dim"], conf["qk_nope_head_dim"],
                conf["v_head_dim"], conf["intermediate_size"],
                conf["moe_intermediate_size"], conf["n_routed_experts"],
                conf["n_shared_experts"], conf["num_experts_per_tok"],
                conf["first_k_dense_replace"], conf["num_hidden_layers"],
                conf["vocab_size"], float(conf["rms_norm_eps"]),
                float(conf["rope_theta"]), conf["assumed"]["dtype"])


# -- weights ---------------------------------------------------------------

def _dense(key, d_in, d_out, dtype):
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * d_in ** -0.5).astype(dtype)


@partial(jax.jit, static_argnums=1)
def _embedding(key, m: Dims):
    k_emb = jax.random.split(key, 4)[0]
    return (jax.random.normal(k_emb, (m.vocab, m.d), jnp.float32)
            * 0.02).astype(m.dtype)


@partial(jax.jit, static_argnums=1)
def _head(key, m: Dims):
    return _dense(jax.random.split(key, 4)[2], m.d, m.vocab, m.dtype)


def _layer_keys(key, m: Dims):
    return jax.random.split(jax.random.split(key, 4)[1], m.layers)


@partial(jax.jit, static_argnums=1)
def _attn_weights(layer_key, m: Dims):
    ks = jax.random.split(jax.random.split(layer_key, 3)[0], 8)
    qk = m.nope + m.rope
    t = m.dtype
    w = {"dkv": _dense(ks[0], m.d, m.kv_rank + m.rope, t),
         "uk": _dense(ks[1], m.kv_rank, m.heads * m.nope, t).reshape(
             m.kv_rank, m.heads, m.nope),
         "uv": _dense(ks[2], m.kv_rank, m.heads * m.v, t).reshape(
             m.kv_rank, m.heads, m.v),
         "o": _dense(ks[3], m.heads * m.v, m.d, t).reshape(m.heads, m.v, m.d)}
    if m.q_rank:
        w["dq"] = _dense(ks[4], m.d, m.q_rank, t)
        w["uq"] = _dense(ks[5], m.q_rank, m.heads * qk, t).reshape(
            m.q_rank, m.heads, qk)
    else:
        w["q"] = _dense(ks[4], m.d, m.heads * qk, t).reshape(
            m.d, m.heads, qk)
    return w


def _ffn_weights(key, d, f, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": _dense(k1, d, f, dtype), "up": _dense(k2, d, f, dtype),
            "down": _dense(k3, f, d, dtype)}


@partial(jax.jit, static_argnums=1)
def _dense_weights(layer_key, m: Dims):
    return _ffn_weights(jax.random.split(layer_key, 3)[1], m.d, m.d_dense,
                        m.dtype)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _stack(key, d_in, d_out, shape, dtype):
    return _dense(key, d_in, d_out, dtype).reshape(shape)


def _moe_weights(layer_key, m: Dims):
    """One program per expert stack, so the float32 draw of only one
    stack is ever live beside the stacks already drawn."""
    k1, k2, k3, k4, k5 = jax.random.split(
        jax.random.split(layer_key, 3)[1], 5)
    e, d, f, t = m.experts, m.d, m.d_expert, m.dtype
    return {"router": _stack(k1, d, e, (d, e), jnp.float32),
            "gate": _stack(k2, d, e * f, (e, d, f), t),
            "up": _stack(k3, d, e * f, (e, d, f), t),
            "down": _stack(k4, f, e * d, (e, f, d), t),
            "shared": _shared_weights(k5, m)}


@partial(jax.jit, static_argnums=1)
def _shared_weights(key, m: Dims):
    return _ffn_weights(key, m.d, m.shared * m.d_expert, m.dtype)


# -- arithmetic ------------------------------------------------------------

def _quant(t, axes, mode: str):
    """Round to ``mode`` (int8 or fp8 e4m3) with one scale per slice over
    ``axes`` that maps the slice's largest magnitude to the format's
    largest (127 or 448); float32 leaves ``t`` as it is."""
    if mode == "f32":
        return t
    top = {"int8": 127.0, "fp8": 448.0}[mode]
    s = jnp.max(jnp.abs(t), axis=axes, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    if mode == "int8":
        return jnp.clip(jnp.round(t / s), -127, 127) * s
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode: str, nc: int = 1):
    """Contract the last ``nc`` axes of activation ``x`` with the first
    ``nc`` axes of weight ``w``, in float32 (or a control's precision)."""
    x = _quant(x.astype(jnp.float32), tuple(range(x.ndim - nc, x.ndim)),
               mode)
    w = _quant(w.astype(jnp.float32), tuple(range(nc)), mode)
    return jnp.tensordot(x, w, axes=nc, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate the last axis of x (..., L, [H,] r) by positions pos (L,),
    halves convention."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _ffn(x, w, mode):
    g = _mm(x, w["gate"], mode)
    return _mm(jax.nn.silu(g) * _mm(x, w["up"], mode), w["down"], mode)


@partial(jax.jit, static_argnums=(2, 3))
def _attn_block(x, w, m: Dims, mode: str):
    """x (B, L, d) -> x + attention(norm(x)), one sequence at a time."""
    qk = m.nope + m.rope

    def one(xs):
        h = _rms(xs, m.eps)
        n = xs.shape[0]
        pos = jnp.arange(n)
        if m.q_rank:
            q = _mm(_rms(_mm(h, w["dq"], mode), m.eps), w["uq"], mode)
        else:
            q = _mm(h, w["q"], mode)
        q_nope, q_pe = q[..., :m.nope], _rope(q[..., m.nope:], pos, m.theta)
        kv = _mm(h, w["dkv"], mode)
        c = _rms(kv[:, :m.kv_rank], m.eps)
        k_pe = _rope(kv[:, m.kv_rank:], pos, m.theta)
        k_nope = _mm(c, w["uk"], mode)
        v = _mm(c, w["uv"], mode)
        s = (jnp.einsum("lhn,shn->hls", q_nope, k_nope, precision=HIGHEST)
             + jnp.einsum("lhr,sr->hls", q_pe, k_pe, precision=HIGHEST))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :],
                      s * qk ** -0.5, -jnp.inf)
        o = jnp.einsum("hls,shv->lhv", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        return xs + _mm(o, w["o"], mode, nc=2)

    return jax.lax.map(one, x)


@partial(jax.jit, static_argnums=(2, 3))
def _dense_block(x, w, m: Dims, mode: str):
    return x + _ffn(_rms(x, m.eps), w, mode)


@partial(jax.jit, static_argnums=(2, 3))
def _moe_block(x, w, m: Dims, mode: str):
    """x + routed and shared experts of norm(x); every expert runs over
    every token and is weighted by the token's (renormalised) top-k weight,
    zero where the expert is not among its top k."""
    shape = x.shape
    h = _rms(x.reshape(-1, m.d), m.eps)
    probs = jax.nn.softmax(jnp.dot(h, w["router"], precision=HIGHEST), -1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    top = top / (jnp.sum(top, -1, keepdims=True) + 1e-9)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)

    def expert(y, xs):
        wg, wu, wd, cw = xs
        out = _ffn(h, {"gate": wg, "up": wu, "down": wd}, mode)
        return y + cw[:, None] * out, None

    y, _ = jax.lax.scan(expert, _ffn(h, w["shared"], mode),
                        (w["gate"], w["up"], w["down"], comb.T))
    return x + y.reshape(shape)


@partial(jax.jit, static_argnums=(2, 3))
def _logits(h, head, m: Dims, mode: str):
    return _mm(_rms(h, m.eps), head, mode)


@jax.jit
def _numbers(ref, logits, tok):
    """Per position: how far below the reference's best logit lies token
    ``tok``'s, the squared distance of ``logits`` from the reference's,
    the reference's squared norm, and the reference's best token."""
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, tok[:, None], 1)[:, 0]
    return (gap, jnp.sum((logits - ref) ** 2, -1), jnp.sum(ref * ref, -1),
            jnp.argmax(ref, -1))


class Sequence_(NamedTuple):
    tokens: List[int]     # prompt, then every served token but the last
    served: List[int]     # served tokens; served[j] follows position first+j
    first: int            # position whose logits gave served[0]
    logits: np.ndarray    # (len(served), vocab): the logits served from


def _blocks(n: int):
    for b0 in range(0, n, POS_BLOCK):
        yield b0, min(n, b0 + POS_BLOCK)


def _pad(a: np.ndarray) -> np.ndarray:
    out = np.zeros((POS_BLOCK,) + a.shape[1:], a.dtype)
    out[:len(a)] = a
    return out


def compare(conf: dict, seed: int, seqs: Sequence[Sequence_],
            controls: Sequence[str] = ()) -> dict:
    """Run the reference over ``seqs`` and, for each mode in ``controls``,
    the reference in that precision in the program's place. Per sequence
    and position, under ``"program"`` and each control's name: ``gap``
    (how far below the reference's best logit lies the served token, or
    the control's first choice), ``d2`` (squared distance of its logits
    from the reference's) and ``r2`` (the reference's squared norm); and
    ``ref_argmax_match``, whether the reference's best is the served
    token."""
    m = dims(conf)
    key = weight_key(seed)
    names = ("program",) + tuple(controls)
    out = {n: {"gap": [], "d2": [], "r2": []} for n in names}
    out["ref_argmax_match"] = []
    for b0 in range(0, len(seqs), BATCH):
        part = list(seqs[b0:b0 + BATCH])
        length = -(-max(len(s.tokens) for s in part) // LEN_BUCKET) \
            * LEN_BUCKET
        toks = np.zeros((BATCH, length), np.int32)
        for i, s in enumerate(part):
            toks[i, :len(s.tokens)] = s.tokens
        with jax.default_matmul_precision("highest"):
            emb = _embedding(key, m).astype(jnp.float32)
            xs = {n: jnp.take(_quant(emb, (1,), "f32" if n == "program"
                                     else n), jnp.asarray(toks), axis=0)
                  for n in names}
            del emb
            for li, lk in enumerate(_layer_keys(key, m)):
                w = _attn_weights(lk, m)
                xs = {n: _attn_block(x, w, m, _mode(n)) for n, x in xs.items()}
                if li < m.first_dense:
                    w, block = _dense_weights(lk, m), _dense_block
                else:
                    w, block = _moe_weights(lk, m), _moe_block
                xs = {n: block(x, w, m, _mode(n)) for n, x in xs.items()}
                del w
            head = _head(key, m)
            for i, s in enumerate(part):
                got = {n: {"gap": [], "d2": [], "r2": []} for n in names}
                match = []
                for lo, hi in _blocks(len(s.served)):
                    sl = slice(s.first + lo, s.first + hi)
                    ref = _logits(_pad_dev(xs["program"][i, sl]), head, m,
                                  "f32")
                    for n in names:
                        if n == "program":
                            lg = jnp.asarray(_pad(np.asarray(
                                s.logits[lo:hi], np.float32)))
                            tok = jnp.asarray(_pad(np.asarray(
                                s.served[lo:hi], np.int32)))
                        else:
                            lg = _logits(_pad_dev(xs[n][i, sl]), head, m, n)
                            tok = jnp.argmax(lg, -1).astype(jnp.int32)
                        gap, d2, r2, top = (np.asarray(a)[:hi - lo] for a in
                                            _numbers(ref, lg, tok))
                        for k, v in (("gap", gap), ("d2", d2), ("r2", r2)):
                            got[n][k].append(v)
                        if n == "program":
                            match.append(top == np.asarray(s.served[lo:hi]))
                for n in names:
                    for k in ("gap", "d2", "r2"):
                        out[n][k].append(np.concatenate(got[n][k]))
                out["ref_argmax_match"].append(np.concatenate(match))
            del xs, head
    return out


def _mode(name: str) -> str:
    return "f32" if name == "program" else name


def _pad_dev(h):
    """Pad a (n, d) block of hidden states to ``POS_BLOCK`` rows."""
    return jnp.zeros((POS_BLOCK, h.shape[-1]), h.dtype).at[:h.shape[0]].set(h)
