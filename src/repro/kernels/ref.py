"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret=True
on CPU; real TPUs at deploy time).  They are deliberately written in the most
obvious way -- no tiling, no streaming -- so correctness is easy to audit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# TTL expected-cost scan (paper §3.2.2) -- oracle
# ---------------------------------------------------------------------------

def _exclusive_suffix_sum(x: jax.Array) -> jax.Array:
    """``out[:, j] = sum(x[:, j+1:])``."""
    tail = jnp.cumsum(x[:, :0:-1], axis=1)[:, ::-1]
    return jnp.concatenate([tail, jnp.zeros_like(x[:, :1])], axis=1)


def ttl_cost_ref(
    hist: jax.Array,        # [E, C] bytes re-read per cell (float32, GB units ok)
    time_w: jax.Array,      # [E, C] sum(gap * bytes) per cell
    last: jax.Array,        # [E, C] paused-bytes census per age cell
    edges: jax.Array,       # [C]   cell upper boundaries (seconds)
    s_price: jax.Array,     # [E]   storage $ / (byte * second) at the target
    n_price: jax.Array,     # [E]   egress  $ / byte on the edge
    first_remote: jax.Array,  # [E] bytes whose initial GET was remote
) -> jax.Array:
    """ExpectedCost(TTL=edges[j]) for every edge and candidate: [E, C].

    Mirrors :func:`repro.core.ttl_policy.expected_cost_curve` (candidate
    TTL=0 is handled by the wrapper, not the kernel).
    """
    e = edges[None, :]
    s = s_price[:, None]
    n = n_price[:, None]
    lower = jnp.concatenate([jnp.zeros_like(edges[:1]), edges[:-1]])
    mid = (0.5 * (lower + edges))[None, :]

    hit_csum = jnp.cumsum(jnp.where(hist > 0, time_w, 0.0), axis=1)
    age_csum = jnp.cumsum(last * mid, axis=1)
    # Suffix sums summed directly, not as total - prefix: the difference
    # cancels in float32 on the long-TTL candidates (see ttl_scan).
    miss = _exclusive_suffix_sum(hist)
    tail = _exclusive_suffix_sum(last)
    return (
        first_remote[:, None] * n
        + s * hit_csum
        + miss * (n + e * s)
        + tail * e * s
        + s * age_csum
    )


# ---------------------------------------------------------------------------
# Streaming (flash) attention -- oracle
# ---------------------------------------------------------------------------

def mha_ref(
    q: jax.Array,           # [B, H, Sq, D]
    k: jax.Array,           # [B, H, Skv, D]
    v: jax.Array,           # [B, H, Skv, D]
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Plain softmax attention.  ``q_offset`` positions q in the kv timeline
    (decode: q_offset = kv_len - q_len)."""
    *_, sq, d = q.shape
    skv = k.shape[-2]
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# RWKV6-style gated linear recurrence -- oracle
# ---------------------------------------------------------------------------

def rwkv6_ref(
    r: jax.Array,           # [B, H, T, K] receptance
    k: jax.Array,           # [B, H, T, K] key
    v: jax.Array,           # [B, H, T, V] value
    w: jax.Array,           # [B, H, T, K] per-step decay (0 < w < 1)
    u: jax.Array,           # [H, K]       bonus for the current token
    state: jax.Array | None = None,  # [B, H, K, V]
) -> tuple[jax.Array, jax.Array]:
    """Finch recurrence (arXiv:2404.05892):
        S_t = diag(w_t) S_{t-1} + k_t^T v_t
        o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
    Returns (out [B,H,T,V], final state [B,H,K,V]).
    """
    B, H, T, K = r.shape
    V = v.shape[-1]
    f32 = jnp.float32
    r, k, v, w = (x.astype(f32) for x in (r, k, v, w))
    s0 = jnp.zeros((B, H, K, V), f32) if state is None else state.astype(f32)

    def step(s, xs):
        rt, kt, vt, wt = xs                      # [B,H,K],[B,H,K],[B,H,V],[B,H,K]
        kv = kt[..., :, None] * vt[..., None, :]   # [B,H,K,V]
        o = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        s_new = wt[..., :, None] * s + kv
        return s_new, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (r, k, v, w))
    s_fin, out = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(out, 0, 2), s_fin
