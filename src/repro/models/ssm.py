"""Mamba-2 (SSD) mixer block (arXiv:2405.21060) + single-token decode.

Layout follows the reference: in_proj produces [z_gate, x, B, C, dt];
depthwise causal conv over (x, B, C); SSD scan (Pallas intra-chunk kernel
+ jnp inter-chunk recurrence); gated RMSNorm; out_proj.

Decode carries (conv_state (B, KC-1, conv_dim), ssm_state (B, H, N, P)) —
O(1) memory per step, which is what makes the long_500k cell feasible
for the SSM/hybrid architectures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd.ops import ssd, ssd_chunked, ssd_decode_step
from repro.models.layers import init_rmsnorm, rmsnorm
from repro.models.module import dense_init, ones_init, zeros_init

CONV_K = 4


def ssm_dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state
    return d_inner, heads, conv_dim


def init_ssm(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    n = cfg.ssm_state
    d_inner, heads, conv_dim = ssm_dims(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    proj_out = 2 * d_inner + 2 * n + heads  # z, x, B, C, dt
    p = {
        "in_proj": dense_init(k1, (d, proj_out), dtype),
        "conv_w": dense_init(k2, (CONV_K, conv_dim), dtype, scale=0.5),
        "conv_b": zeros_init((conv_dim,), dtype),
        "a_log": jnp.log(
            jnp.linspace(1.0, 16.0, heads).astype(jnp.float32)
        ),
        "dt_bias": zeros_init((heads,), jnp.float32),
        "d_skip": ones_init((heads,), jnp.float32),
        "out_proj": dense_init(k3, (d_inner, d), dtype),
    }
    nrm, nrm_a = init_rmsnorm(d_inner, dtype)
    p["norm"] = nrm
    a = {
        "in_proj": ("embed", "ssm_inner"),
        "conv_w": (None, "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "a_log": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "d_skip": ("ssm_heads",),
        "out_proj": ("ssm_inner", "embed"),
        "norm": nrm_a,
    }
    return p, a


def _split_proj(cfg, h):
    d_inner, heads, _ = ssm_dims(cfg)
    n = cfg.ssm_state
    z, xbc_dt = jnp.split(h, [d_inner], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [d_inner + 2 * n], axis=-1)
    return z, xbc, dt  # gate, conv input, dt (B,S,H)


def _causal_conv(p, xbc, conv_state=None):
    """Depthwise causal conv, kernel CONV_K. xbc: (B, S, C)."""
    w = p["conv_w"].astype(xbc.dtype)  # (K, C)
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], CONV_K - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)  # (B, S+K-1, C)
    out = sum(
        xp[:, i : i + xbc.shape[1]] * w[i] for i in range(CONV_K)
    ) + p["conv_b"].astype(xbc.dtype)
    new_state = xp[:, -(CONV_K - 1) :]
    return jax.nn.silu(out), new_state


def ssm_mixer(p, cfg, x, h0=None, conv_state=None, *, chunk=64):
    """Full-sequence SSD. x: (B, S, D).

    Returns (out, (conv_state, ssm_state))."""
    d_inner, heads, _ = ssm_dims(cfg)
    n = cfg.ssm_state
    b, s, _ = x.shape
    h = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = _split_proj(cfg, h)
    xbc, conv_state = _causal_conv(p, xbc, conv_state)
    xi, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])  # (H,) negative
    xh = xi.reshape(b, s, heads, cfg.ssm_head_dim)
    bm = jnp.broadcast_to(bmat[:, :, None, :], (b, s, heads, n))
    cm = jnp.broadcast_to(cmat[:, :, None, :], (b, s, heads, n))

    if cfg.use_kernels:
        y, hf = ssd(
            xh.astype(jnp.float32), dt, a, bm.astype(jnp.float32),
            cm.astype(jnp.float32), h0, chunk=min(chunk, s),
            use_kernel=True, interpret=jax.default_backend() != "tpu",
        )
    else:
        # loop-free chunked SSD: the XLA production path (see ssd/ops.py)
        y, hf = ssd_chunked(
            xh.astype(jnp.float32), dt, a, bm.astype(jnp.float32),
            cm.astype(jnp.float32), h0, chunk=min(chunk, s),
        )
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, (conv_state, hf)


def ssm_decode(p, cfg, x, state):
    """Single-token step. x: (B, 1, D); state = (conv_state, ssm_state)."""
    conv_state, hprev = state
    d_inner, heads, _ = ssm_dims(cfg)
    n = cfg.ssm_state
    b = x.shape[0]
    h = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(x.dtype))
    z, xbc, dt = _split_proj(cfg, h)
    xbc, conv_state = _causal_conv(p, xbc, conv_state)
    xi, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)

    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["a_log"])
    xh = xi[:, 0].reshape(b, heads, cfg.ssm_head_dim).astype(jnp.float32)
    bm = jnp.broadcast_to(bmat[:, 0, None, :], (b, heads, n)).astype(jnp.float32)
    cm = jnp.broadcast_to(cmat[:, 0, None, :], (b, heads, n)).astype(jnp.float32)
    yt, hnew = ssd_decode_step(xh, dt1, a, bm, cm, hprev)
    yt = yt + xh * p["d_skip"][None, :, None]
    y = yt.reshape(b, 1, d_inner).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, (conv_state, hnew)


def init_ssm_cache(cfg, batch: int):
    d_inner, heads, conv_dim = ssm_dims(cfg)
    return (
        jnp.zeros((batch, CONV_K - 1, conv_dim), jnp.float32),
        jnp.zeros((batch, heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
    )
