"""State-space mixers: Mamba-2 (SSD) and Jamba's Mamba-1.

``cfg.ssm.kind`` picks the mixer; :func:`mamba_init`,
:func:`mamba_layer` and :func:`mamba_cache_shapes` dispatch on it.

SSD (``"ssd"``, arXiv:2405.21060): training/prefill uses the chunked SSD
algorithm (§6): within-chunk "attention-like" matmuls (MXU-friendly)
plus a ``lax.scan`` recurrence over chunk states. Decode is the O(1)
recurrent update. State layout: (B, H, P, N) with H = ssm heads (TP over
"model"), P = head_dim, N = d_state. Conv cache: (B, K-1, conv_channels).

Mamba-1 (``"mamba1"``, arXiv:2312.00752, as Jamba publishes it,
arXiv:2403.19887 and Hugging Face ``JambaMambaMixer``)::

    x, z    = in_proj(h)
    x       = silu(causal_depthwise_conv(x) + conv_bias)
    dt, B, C = split(x_proj(x), [dt_rank, N, N]); each RMS-normed
    delta   = softplus(dt_proj(dt) + dt_bias)
    s_t     = exp(delta_t * A) * s_{t-1} + (delta_t * x_t) B_t,  A = -exp(A_log)
    y_t     = s_t . C_t + D * x_t
    out     = out_proj(y * silu(z))

A is per channel and state (d_inner, N); no projection bias, no inner
norm.  The state (B, d_inner, N) and the scan stay in f32.  Prefill runs
the Pallas selective scan (``kernels/selective_scan.py``), which keeps
the state on chip and never writes a (token, channel, state) tensor;
training runs the same recurrence as a ``lax.scan`` over positions (it
has a gradient); decode is the one-step update.  Conv cache:
(B, K-1, d_inner).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import dense_init, rmsnorm, rmsnorm_init

Params = Dict[str, Any]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    return s, d, di, nh, s.n_groups, s.d_state, s.d_conv, s.head_dim


def _dt_bias(key, shape, dtype):
    """The inverse softplus of a dt drawn log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) *
                 (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def mamba_init(key, cfg: ModelConfig, dtype) -> Params:
    if cfg.ssm.kind == "mamba1":
        return mamba1_init(key, cfg, dtype)
    s, d, di, nh, ng, ds, k, hp = _dims(cfg)
    conv_ch = di + 2 * ng * ds
    proj_out = 2 * di + 2 * ng * ds + nh
    ks = jax.random.split(key, 4)
    return {
        "w_zxbcdt": dense_init(ks[0], (d, proj_out), dtype),
        "conv_w": dense_init(ks[1], (k, conv_ch), dtype, fan_in=k),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "dt_bias": _dt_bias(ks[2], (nh,), jnp.float32),
        "ssm_D": jnp.ones((nh,), jnp.float32),
        "ssm_norm": jnp.zeros((di,), dtype),
        "w_ssm_out": dense_init(ks[3], (di, d), dtype),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    s, d, di, nh, ng, ds, k, hp = _dims(cfg)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * ng * ds]
    dt = zxbcdt[..., -nh:]
    return z, xbc, dt


def _causal_conv(xbc, conv_w, conv_b, prev: Optional[jnp.ndarray] = None):
    """Depthwise causal conv along S. xbc: (B,S,CH); conv_w: (K,CH).

    prev: optional (B, K-1, CH) history prepended (decode/chunked prefill).
    Returns (out (B,S,CH), tail (B,K-1,CH))."""
    k = conv_w.shape[0]
    if prev is None:
        prev = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[-1]), xbc.dtype)
    xp = jnp.concatenate([prev, xbc], axis=1)
    out = sum(xp[:, i:i + xbc.shape[1]] * conv_w[i] for i in range(k))
    out = jax.nn.silu(out + conv_b)
    tail = xp[:, xp.shape[1] - (k - 1):]
    return out, tail


# ---------------------------------------------------------------------------
# Chunked SSD (train / prefill)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, B, C, chunk: int, init_state=None):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,) (negative); B,C: (B,S,G,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N)). f32 internals.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)
    xc = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, g, n).astype(jnp.float32)

    da = dtc * A  # (b, nc, T, h)
    seg = jnp.cumsum(da, axis=2)                     # (b,nc,T,h)
    seg_last = seg[:, :, -1:]                        # (b,nc,1,h)

    # within-chunk (diagonal block) — attention-like
    # L[i,j] = exp(seg_i - seg_j) for i >= j
    li = seg[:, :, :, None, :] - seg[:, :, None, :, :]   # (b,nc,T,T,h)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(li), 0.0)
    # scores G[i,j] per head: C_i . B_j  (group-broadcast to heads)
    Gm = jnp.einsum("bctgn,bcsgn->bctsg", Cc, Bc)        # (b,nc,T,T,g)
    Gm = jnp.repeat(Gm, hg, axis=-1)                     # heads
    M = Gm * L * dtc[:, :, None, :, :]                   # weight dt_j
    y_diag = jnp.einsum("bctsh,bcshp->bcthp", M, xc)

    # chunk-local end states: sum_j exp(seg_last - seg_j) dt_j B_j x_j
    decay = jnp.exp(seg_last - seg)                      # (b,nc,T,h)
    dtx = (dtc * decay)[..., None] * xc                  # (b,nc,T,h,p)
    Bh = jnp.repeat(Bc, hg, axis=3)                      # (b,nc,T,h,n)
    s_local = jnp.einsum("bcthn,bcthp->bchpn", Bh, dtx)  # (b,nc,h,p,n)

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(seg_last[:, :, 0])             # (b,nc,h)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def body(carry, inp):
        s_loc, dec = inp                                 # (b,h,p,n), (b,h)
        new = carry * dec[:, :, None, None] + s_loc
        return new, carry                                # emit state BEFORE

    final, prev_states = jax.lax.scan(
        body, init_state.astype(jnp.float32),
        (jnp.moveaxis(s_local, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (b,nc,h,p,n)

    # off-diagonal: y_off[i] = exp(seg_i) * C_i . S_prev
    Ch = jnp.repeat(Cc, hg, axis=3)                      # (b,nc,T,h,n)
    y_off = jnp.einsum("bcthn,bchpn->bcthp", Ch, prev_states)
    y_off = y_off * jnp.exp(seg)[..., None]
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final


def ssd_step(state, x, dt, A, B, C):
    """One recurrent step. state: (B,H,P,N); x: (B,H,P); dt: (B,H);
    B,C: (B,G,N). Returns (y (B,H,P), new_state)."""
    b, h, p, n = state.shape
    g = B.shape[1]
    hg = h // g
    Bh = jnp.repeat(B, hg, axis=1)                       # (b,h,n)
    Ch = jnp.repeat(C, hg, axis=1)
    da = jnp.exp(dt * A)                                 # (b,h)
    upd = (dt[..., None] * x)[..., None] * Bh[:, :, None, :]   # (b,h,p,n)
    new = state * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    return y, new


# ---------------------------------------------------------------------------
# Layer entry point
# ---------------------------------------------------------------------------


def mamba_layer(params, x, *, cfg: ModelConfig, mode: str,
                cache: Optional[Params] = None):
    """x: (B,S,D) -> (y (B,S,D), new_cache or None)."""
    if cfg.ssm.kind == "mamba1":
        return mamba1_layer(params, x, cfg=cfg, mode=mode, cache=cache)
    s_cfg, d, di, nh, ng, ds, k, hp = _dims(cfg)
    b, s, _ = x.shape
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["w_zxbcdt"])
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    if mode == "decode":
        assert cache is not None
        conv_out, conv_tail = _causal_conv(
            xbc, params["conv_w"], params["conv_b"], prev=cache["conv"])
        xs = conv_out[..., :di].reshape(b, nh, hp).astype(jnp.float32)
        Bm = conv_out[..., di:di + ng * ds].reshape(b, ng, ds)
        Cm = conv_out[..., di + ng * ds:].reshape(b, ng, ds)
        y, new_state = ssd_step(
            cache["ssm"].astype(jnp.float32), xs, dt[:, 0], A,
            Bm[:, :].astype(jnp.float32), Cm.astype(jnp.float32))
        y = y + params["ssm_D"][:, None] * xs
        y = y.reshape(b, 1, di)
        new_cache = {"ssm": new_state, "conv": conv_tail}
    else:
        prev_conv = cache["conv"] if cache is not None else None
        init_state = cache["ssm"] if cache is not None else None
        conv_out, conv_tail = _causal_conv(
            xbc, params["conv_w"], params["conv_b"], prev=prev_conv)
        xs = conv_out[..., :di].reshape(b, s, nh, hp)
        Bm = conv_out[..., di:di + ng * ds].reshape(b, s, ng, ds)
        Cm = conv_out[..., di + ng * ds:].reshape(b, s, ng, ds)
        chunk = min(s_cfg.chunk, s)
        pad = (-s) % chunk
        if pad:
            # zero-pad to a chunk multiple; dt=0 on padding makes the padded
            # steps identity transitions (no decay, no contribution)
            xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        y, final = ssd_chunked(xs, dt, A, Bm, Cm, chunk,
                               init_state=init_state)
        if pad:
            y = y[:, :s]
            xs = xs[:, :s]
        y = y + params["ssm_D"][None, None, :, None] * \
            xs.astype(jnp.float32)
        y = y.reshape(b, s, di)
        new_cache = None
        if mode == "prefill":
            new_cache = {"ssm": final, "conv": conv_tail}

    y = y.astype(x.dtype) * jax.nn.silu(z)
    y = rmsnorm(y, params["ssm_norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, params["w_ssm_out"]), new_cache


def mamba_cache_shapes(cfg: ModelConfig, batch: int):
    if cfg.ssm.kind == "mamba1":
        di = cfg.ssm.d_inner(cfg.d_model)
        return {"ssm": (batch, di, cfg.ssm.d_state),
                "conv": (batch, cfg.ssm.d_conv - 1, di)}
    s, d, di, nh, ng, ds, k, hp = _dims(cfg)
    return {"ssm": (batch, nh, hp, ds), "conv": (batch, k - 1,
                                                 di + 2 * ng * ds)}


# ---------------------------------------------------------------------------
# Mamba-1 (Jamba's mixer)
# ---------------------------------------------------------------------------


def mamba1_init(key, cfg: ModelConfig, dtype) -> Params:
    s, d = cfg.ssm, cfg.d_model
    di, n, r = s.d_inner(d), s.d_state, s.dt_rank
    ks = jax.random.split(key, 6)
    a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))
    return {
        "w_xz": dense_init(ks[0], (d, 2, di), dtype, fan_in=d),
        "conv_w": dense_init(ks[1], (s.d_conv, di), dtype, fan_in=s.d_conv),
        "conv_b": jnp.zeros((di,), dtype),
        "w_dbc": dense_init(ks[2], (di, r + 2 * n), dtype),
        "dt_norm": rmsnorm_init(r, dtype),
        "b_norm": rmsnorm_init(n, dtype),
        "c_norm": rmsnorm_init(n, dtype),
        "w_dt": dense_init(ks[3], (r, di), dtype),
        "dt_bias": _dt_bias(ks[4], (di,), dtype),
        "A_log": jnp.log(a).astype(dtype),
        "ssm_D": jnp.ones((di,), dtype),
        "w_ssm_out": dense_init(ks[5], (di, d), dtype),
    }


def selective_step(state, x, dt, A, B, C):
    """One step of the Mamba-1 recurrence.  state: (B, D, N); x, dt:
    (B, D); A: (D, N); B, C: (B, N).  Returns (y (B, D), new state)."""
    new = jnp.exp(dt[:, :, None] * A) * state + \
        (dt * x)[:, :, None] * B[:, None, :]
    return jnp.sum(new * C[:, None, :], axis=-1), new


def selective_scan_seq(x, dt, A, B, C, init_state):
    """The recurrence over positions, one ``lax.scan`` step each.
    x, dt: (B, S, D); B, C: (B, S, N).  Returns (y (B, S, D), final)."""
    def step(s, inp):
        y, s = selective_step(s, *inp[:2], A, *inp[2:])
        return s, y

    final, ys = jax.lax.scan(step, init_state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1), final


def mamba1_layer(params, x, *, cfg: ModelConfig, mode: str,
                 cache: Optional[Params] = None):
    """x: (B,S,D) -> (y (B,S,D), new_cache or None)."""
    s_cfg = cfg.ssm
    di, n, r = s_cfg.d_inner(cfg.d_model), s_cfg.d_state, s_cfg.dt_rank
    b = x.shape[0]
    f32 = jnp.float32
    xz = jnp.einsum("bsd,dke->bske", x, params["w_xz"])
    xs, z = xz[:, :, 0], xz[:, :, 1]
    xs, conv_tail = _causal_conv(
        xs, params["conv_w"], params["conv_b"],
        prev=cache["conv"] if cache is not None else None)
    dbc = jnp.einsum("bse,er->bsr", xs, params["w_dbc"])
    dt = rmsnorm(dbc[..., :r], params["dt_norm"], cfg.norm_eps)
    Bm = rmsnorm(dbc[..., r:r + n], params["b_norm"], cfg.norm_eps).astype(f32)
    Cm = rmsnorm(dbc[..., r + n:], params["c_norm"], cfg.norm_eps).astype(f32)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt, params["w_dt"],
                   preferred_element_type=f32)
        + params["dt_bias"].astype(f32))
    A = -jnp.exp(params["A_log"].astype(f32))
    state = (cache["ssm"].astype(f32) if cache is not None
             else jnp.zeros((b, di, n), f32))

    if mode == "decode":
        y, state = selective_step(state, xs[:, 0].astype(f32), dt[:, 0], A,
                                  Bm[:, 0], Cm[:, 0])
        y = y[:, None]
    elif mode == "prefill":
        y, state = ops.selective_scan(xs, dt, A, Bm, Cm, state)
    else:
        y, state = selective_scan_seq(xs.astype(f32), dt, A, Bm, Cm, state)
    y = y + params["ssm_D"].astype(f32) * xs.astype(f32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, params["w_ssm_out"])
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"ssm": state, "conv": conv_tail}
    return out, new_cache
