"""Plain float32 reference of a dense decoder with grouped-query attention:
the Qwen2.5 (bias on q, k, v) and Qwen3 (RMSNorm on each head of q and k)
families, as their papers and configs describe them.

  x = embed[tokens]
  per layer:  h = norm1(x); q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              [qwen3: q, k = rmsnorm_head(q), rmsnorm_head(k)]
              q, k = rope(q), rope(k)          (rotate-half, base rope_theta)
              x = x + softmax(q k^T / sqrt(hd), causal) v Wo
              x = x + (silu(norm2(x) Wg) * norm2(x) Wu) Wd
  logits = norm(x) W_head                      (untied)

RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)`` (the scale is stored
as an offset from one). Everything runs in float32 at ``highest`` matmul
precision, one layer at a time, so that it fits beside nothing else on the
chip. It imports nothing of the program; the weights come from the
benchmark's own seeded generator (``harness.weights``).

``quant="fp8"`` is the control: every linear layer's inputs and weights
rounded to float8 e4m3, weights scaled per output channel and activations
per token, as an fp8 serving path would. Attention stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

#: query rows per attention block (keeps scores at rows x S x heads)
Q_BLOCK = 256
FP8_MAX = 448.0


def param_spec(m: dict) -> dict:
    """Path of the served parameter tree -> (shape, std, per_layer)."""
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    hd, hq, hkv, ff = m["head_dim"], m["num_heads"], m["num_kv_heads"], m["d_ff"]
    spec = {
        ("embed",): ((V, d), 0.02, False),
        ("final_norm", "scale"): ((d,), 0.1, False),
        ("lm_head",): ((d, V), 0.02, False),
        ("layers", "ln1", "scale"): ((L, d), 0.1, True),
        ("layers", "ln2", "scale"): ((L, d), 0.1, True),
        ("layers", "attn", "wq", "w"): ((L, d, hq * hd), 0.02, True),
        ("layers", "attn", "wk", "w"): ((L, d, hkv * hd), 0.02, True),
        ("layers", "attn", "wv", "w"): ((L, d, hkv * hd), 0.02, True),
        ("layers", "attn", "wo", "w"): ((L, hq * hd, d),
                                        0.02 / np.sqrt(2 * L), True),
        ("layers", "mlp", "gate", "w"): ((L, d, ff), 0.02, True),
        ("layers", "mlp", "up", "w"): ((L, d, ff), 0.02, True),
        ("layers", "mlp", "down", "w"): ((L, ff, d), 0.02 / np.sqrt(2), True),
    }
    if m.get("attn_bias"):
        for w, n in (("wq", hq), ("wk", hkv), ("wv", hkv)):
            spec[("layers", "attn", w, "b")] = ((L, n * hd), 0.1, True)
    if m.get("qk_norm"):
        spec[("layers", "attn", "q_norm", "scale")] = ((L, hd), 0.1, True)
        spec[("layers", "attn", "k_norm", "scale")] = ((L, hd), 0.1, True)
    return spec


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(p, x, n_valid, *, m, quant):
    """One layer over a padded sequence ``x`` (S, d); rows >= n_valid are
    padding, later than every real row, so the causal mask hides them."""
    m = dict(m)
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rmsnorm(x, p[("layers", "ln1", "scale")], eps)
    q = _linear(h, p[("layers", "attn", "wq", "w")], quant)
    k = _linear(h, p[("layers", "attn", "wk", "w")], quant)
    v = _linear(h, p[("layers", "attn", "wv", "w")], quant)
    if m.get("attn_bias"):
        q = q + p[("layers", "attn", "wq", "b")]
        k = k + p[("layers", "attn", "wk", "b")]
        v = v + p[("layers", "attn", "wv", "b")]
    q, k, v = (q.reshape(S, hq, hd), k.reshape(S, hkv, hd),
               v.reshape(S, hkv, hd))
    if m.get("qk_norm"):
        q = _rmsnorm(q, p[("layers", "attn", "q_norm", "scale")], eps)
        k = _rmsnorm(k, p[("layers", "attn", "k_norm", "scale")], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)                      # (S, hq, hd)
    v = jnp.repeat(v, g, axis=1)

    def block(qb, start):
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        qpos = start + jnp.arange(qb.shape[0])
        keep = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    nb = S // Q_BLOCK
    a = jax.lax.map(lambda i: block(
        jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK), i * Q_BLOCK),
        jnp.arange(nb))
    a = a.reshape(S, hq * hd)
    x = x + _linear(a, p[("layers", "attn", "wo", "w")], quant)
    h = _rmsnorm(x, p[("layers", "ln2", "scale")], eps)
    gate = _linear(h, p[("layers", "mlp", "gate", "w")], quant)
    up = _linear(h, p[("layers", "mlp", "up", "w")], quant)
    return x + _linear(jax.nn.silu(gate) * up,
                       p[("layers", "mlp", "down", "w")], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, scale, w, *, eps, quant):
    return _linear(_rmsnorm(x, scale, eps), w, quant)


def logits(m: dict, seed: int, seqs: list[list[int]], rows: list[list[int]],
           quant: str | None = None) -> list[np.ndarray]:
    """For each token sequence, the float32 logits at the given row indices
    (row r predicts token r + 1). Weights are regenerated from ``seed``
    layer by layer; all sequences advance through a layer together."""
    spec = param_spec(m)
    dt = jnp.dtype(m["dtype"])
    frozen = tuple(sorted(m.items()))
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        embed = weights.leaf(spec, seed, ("embed",), dt)
        xs, valid = [], []
        for s in seqs:
            # a power of two (at least 1024) bounds the compiled shapes
            S = max(1024, 1 << (len(s) - 1).bit_length())
            ids = np.zeros(S, np.int32)
            ids[: len(s)] = s
            xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
            valid.append(len(s))
        del embed
        for i in range(m["num_layers"]):
            p = f32(weights.layer_params(spec, seed, i, dt))
            xs = [_layer(p, x, n, m=frozen, quant=quant)
                  for x, n in zip(xs, valid)]
            del p
        scale = weights.leaf(spec, seed, ("final_norm", "scale"), dt)
        head = weights.leaf(spec, seed, ("lm_head",), dt).astype(jnp.float32)
        out = []
        for x, r in zip(xs, rows):
            idx = np.zeros(max(64, 1 << (len(r) - 1).bit_length()), np.int32)
            idx[: len(r)] = r
            lg = _head(x[jnp.asarray(idx)], scale.astype(jnp.float32), head,
                       eps=m["norm_eps"], quant=quant)
            out.append(np.asarray(lg)[: len(r)])
    return out
