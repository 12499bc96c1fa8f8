"""Selective state-space scan (Mamba-2's SSD form; Dao & Gu 2024,
arXiv:2405.21060) in plain `jax.numpy` / `lax`: no kernel yet.

Per head h (its B and C shared by the heads of its group), per token t:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S in R^{p x n}
    y_t = S_t C_t

`ssd_chunked_scan` computes a whole sequence in chunks: inside a chunk the
outputs are one decay-masked (C B^T) product against x (matmuls, MXU
work), between chunks the state is carried, so the sequential depth is
len / chunk. It takes a carried-in state and returns the carried-out one
(chunked prefill hands the state from call to call), and a token whose
`dt` is 0 neither decays nor feeds the state: that is how a padded bucket
tail is kept out of it. `ssm_step` is the recurrence itself for one token,
the decode path.

Precision: the decays (sums and exponentials of dt A) and the state are
float32 whatever the inputs are; the two products that read or write the
state run at `Precision.HIGHEST`, so on a TPU the float32 state is not
rounded to bf16 on its way through the MXU (they are a few hundred MFLOP a
call: the state's bytes, not these FLOPs, are what a step pays for).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def ssm_step(x, dt, a, b, c, state):
    """One token of every row. x [rows, h, p]; dt [rows, h] (>= 0; 0
    leaves the row's state as it is); a [h] (< 0); b, c [rows, g, n];
    state [rows, h, p, n]. Returns y [rows, h, p] float32 and the new
    state in the state's own type. Elementwise over the state (one read,
    one write), so a donated state is updated in place."""
    heads = x.shape[1]
    per_group = heads // b.shape[1]
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))                       # [rows, h]
    bh = jnp.repeat(b.astype(F32), per_group, axis=1)         # [rows, h, n]
    ch = jnp.repeat(c.astype(F32), per_group, axis=1)
    fed = (dt[..., None] * x.astype(F32))[..., None] * bh[:, :, None, :]
    new = state.astype(F32) * decay[..., None, None] + fed
    y = (new * ch[:, :, None, :]).sum(-1)
    return y, new.astype(state.dtype)


def ssd_chunked_scan(x, dt, a, b, c, state, chunk: int = 128):
    """x [batch, len, h, p]; dt [batch, len, h] (>= 0, 0 at positions that
    must not touch the state); a [h] (< 0); b, c [batch, len, g, n];
    state [batch, h, p, n] float32, the state before the first token.
    Returns y [batch, len, h, p] float32 and the state after the last
    token, float32. A length under `chunk` is one short chunk; any other
    is padded with dt = 0 to whole chunks."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per_group = heads // groups
    q = min(chunk, length)
    pad = -length % q
    if pad:
        widen = lambda t: jnp.pad(  # noqa: E731
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    chunks = (length + pad) // q

    def by_chunk(t, *tail):
        # [batch, chunks * q, ...] -> [chunks, batch, q, ...]
        return jnp.moveaxis(t.reshape(batch, chunks, q, *tail), 1, 0)

    xs = by_chunk(x.astype(F32), groups, per_group, p)
    dts = by_chunk(dt.astype(F32), groups, per_group)
    bs = by_chunk(b.astype(F32), groups, n)
    cs = by_chunk(c.astype(F32), groups, n)
    a = a.astype(F32).reshape(groups, per_group)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(carry, inputs):
        xc, dtc, bc, cc = inputs
        s = carry.reshape(batch, groups, per_group, p, n)
        # log-decay from the chunk's start through token t, [b, g, k, t]
        cum = jnp.transpose(jnp.cumsum(dtc * a, axis=1), (0, 2, 3, 1))
        dt_s = jnp.transpose(dtc, (0, 2, 3, 1))
        # within the chunk: y_t = sum_{s<=t} (C_t.B_s) e^{cum_t-cum_s} dt_s x_s
        scores = jnp.einsum("btgn,bsgn->bgts", cc, bc)
        span = cum[..., :, None] - cum[..., None, :]           # [b,g,k,t,s]
        weights = jnp.exp(jnp.where(causal, span, -jnp.inf)) \
            * scores[:, :, None] * dt_s[..., None, :]
        y = jnp.einsum("bgkts,bsgkp->btgkp", weights, xc)
        # from the carried-in state: y_t += e^{cum_t} S_in C_t
        into = jnp.einsum("btgn,bgkpn->btgkp", cc, s, precision=_EXACT)
        y = y + into * jnp.transpose(jnp.exp(cum), (0, 3, 1, 2))[..., None]
        # carried out: S = e^{cum_end} S_in + sum_s e^{cum_end-cum_s} dt_s x_s (x) B_s
        end = cum[..., -1]                                     # [b, g, k]
        to_end = jnp.exp(end[..., None] - cum) * dt_s          # [b,g,k,s]
        fed = jnp.einsum("bsgkp,bsgn->bgkpn",
                         xc * jnp.transpose(to_end, (0, 3, 1, 2))[..., None],
                         bc, precision=_EXACT)
        s = s * jnp.exp(end)[..., None, None] + fed
        return s.reshape(batch, heads, p, n), y

    state, ys = jax.lax.scan(one_chunk, state.astype(F32),
                             (xs, dts, bs, cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(batch, chunks * q, heads, p)
    return y[:, :length], state
