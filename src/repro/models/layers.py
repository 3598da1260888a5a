"""Common layers: norms, rotary embeddings, MLP variants, embeddings.

The vocab-sharded embedding lookup is the BCL DArray-rget specialization:
the table is sharded over the model axis ("hosted" shards), each owner
gathers its hits, and one psum delivers the rows — owner-computes remote
get with a single collective (DESIGN.md section 3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.sharding import Axes, shard


def rms_norm(x, gamma, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma


def rotary(x, positions, theta: float = 1e4):
    """x (..., T, hd) with positions (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., T, half)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def activation_fn(name: str):
    return {
        "gelu": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
        "silu": jax.nn.silu,
    }.get(name, jax.nn.silu)


def mlp(params, x, activation: str = "swiglu"):
    """Gated or plain MLP. params: w_in (D,F), w_out (F,D) [, w_gate (D,F)]."""
    if activation in ("swiglu", "geglu"):
        act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
        h = act(x @ params["w_gate"]) * (x @ params["w_in"])
    else:
        h = activation_fn(activation)(x @ params["w_in"])
    return h @ params["w_out"]


def mlp_init(rng, d: int, f: int, activation: str, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = d ** -0.5
    scale_out = f ** -0.5
    p = {
        "w_in": (jax.random.normal(k1, (d, f)) * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (f, d)) * scale_out).astype(dtype),
    }
    if activation in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k3, (d, f)) * scale_in).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# vocab-sharded embedding: owner-computes rget (BCL DArray specialization)
# ---------------------------------------------------------------------------

def embed_lookup(table, tokens, mesh: Mesh, axes: Axes):
    """table (V, D) sharded P(model, ...); tokens (B, T) sharded over data.

    Each model-rank hosts a vocab shard; it gathers rows for the token ids
    that fall in its range and one psum combines — a batched one-sided
    remote get served by the owner, cost R per token (paper Table 2).
    """
    vsize = table.shape[0]
    nm = mesh.shape[axes.model]
    vloc = vsize // nm
    n_data = 1
    for a in axes.data:
        n_data *= mesh.shape[a]
    lead = axes.data if tokens.shape[0] % n_data == 0 else None

    def f(tbl, tok):
        r = jax.lax.axis_index(axes.model)
        loc = tok.astype(jnp.int32) - r * vloc
        hit = (loc >= 0) & (loc < vloc)
        rows = jnp.where(hit[..., None],
                         tbl[jnp.clip(loc, 0, vloc - 1)], 0)
        return jax.lax.psum(rows, axes.model)

    in_specs = (P(axes.model, None), P(lead, None))
    out_specs = P(lead, None, None)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)(table, tokens)


def embed_lookup_dense(table, tokens):
    """Single-device / serial fallback."""
    return table[tokens]


def output_logits(x, table, mesh: Mesh | None, axes: Axes | None):
    """logits = x @ table.T with vocab sharded over model."""
    logits = jnp.einsum("btd,vd->btv", x, table)
    if mesh is not None:
        logits = shard(logits, mesh, P(axes.data, None, axes.model))
    return logits


def chunked_softmax_xent(x, table, targets, mask, mesh, axes,
                         chunk: int = 512, vocab_real: int | None = None):
    """Cross-entropy over a large sharded vocab without materializing the
    full (B, T, V) logits in one piece: scan over T chunks.

    ``vocab_real`` masks padding rows of the (padded) embedding table out
    of the normalizer."""
    b, t, d = x.shape
    n = t // chunk if t % chunk == 0 else 1
    c = t // n
    vpad = table.shape[0]
    col_ok = (jnp.arange(vpad) < (vocab_real or vpad))[None, None, :]

    def body(carry, xs):
        xc, yc, mc = xs                       # (B, c, D), (B, c), (B, c)
        logits = jnp.einsum("bcd,vd->bcv", xc, table).astype(jnp.float32)
        if mesh is not None:
            logits = shard(logits, mesh, P(axes.data, None, axes.model))
        logits = jnp.where(col_ok, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mc
        return carry + nll.sum(), None

    xs = (x.reshape(b, n, c, d).swapaxes(0, 1),
          targets.reshape(b, n, c).swapaxes(0, 1),
          mask.reshape(b, n, c).swapaxes(0, 1))
    total, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
    return total / jnp.maximum(mask.sum(), 1)
