"""Mamba-1 selective-scan block (Falcon-Mamba), TPU-adapted.

The CUDA selective-scan kernel fuses the recurrence in SRAM.  On a TPU the
full-sequence scan is the Pallas kernel ``kernels/selective_scan.py``,
which holds the state in VMEM and reads dt, B, C, x and z once.  Elsewhere
(and as the kernel's backward) the XLA scan chunks the sequence (``chunk``
tokens at a time) and runs a log-depth ``associative_scan`` *within* each
chunk while carrying the SSM state across chunks with ``lax.scan``: the
(B, S, d_inner, N) discretized tensors only ever exist one chunk at a time.

Jamba's mixer (``cfg.ssm.inner_norms``) adds RMSNorms with learned gains on
``dt``, ``B`` and ``C`` after ``x_proj``.  The projections run under the
``mamba.proj`` named scope and the scan under ``mamba.scan``, so their
device ops carry the scope in the HLO metadata.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sharding import (current_mesh, logical_constraint,
                                 logical_to_pspec)
from repro.core.socket import mem_write
from repro.kernels import ops
from repro.models.layers import _he, rmsnorm


def chunked_linear_scan(a, b, h0, chunk):
    """h_t = a_t * h_{t-1} + b_t  along axis=1 of (B, S, ...) tensors.
    Returns (h_all (B, S, ...), h_last (B, ...))."""
    B, S = a.shape[:2]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk
    rest = a.shape[2:]
    a_r = jnp.moveaxis(a.reshape(B, n, chunk, *rest), 1, 0)
    b_r = jnp.moveaxis(b.reshape(B, n, chunk, *rest), 1, 0)

    def op(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    def body(h, xs):
        ac, bc = xs
        acum, bcum = jax.lax.associative_scan(op, (ac, bc), axis=1)
        h_t = acum * h[:, None] + bcum
        return h_t[:, -1], h_t

    h_last, ys = jax.lax.scan(body, h0, (a_r, b_r))
    h_all = jnp.moveaxis(ys, 0, 1).reshape(B, S, *rest)
    return h_all, h_last


def causal_conv1d(x, w, b, state):
    """Depthwise causal conv.  x: (B, S, C), w: (C, K), state: (B, K-1, C)
    carry-in.  Returns (y (B, S, C), new_state (B, K-1, C))."""
    Kw = w.shape[1]
    xpad = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = sum(xpad[:, j:j + S] * w[:, j].astype(x.dtype) for j in range(Kw))
    if b is not None:
        y = y + b.astype(x.dtype)
    new_state = xpad[:, -(Kw - 1):] if Kw > 1 else state
    return y, new_state


def mamba_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    di = cfg.ssm.expand * d
    N = cfg.ssm.state_dim
    dtr = cfg.ssm.dt_rank or -(-d // 16)
    Kw = cfg.ssm.conv_dim
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (di, 1))
    p = {
        "in_proj": _he(ks[0], (d, 2 * di), dtype),
        "conv_w": (jax.random.normal(ks[1], (di, Kw)) * (Kw ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _he(ks[2], (di, dtr + 2 * N), dtype, fan_in=di),
        "dt_proj": _he(ks[3], (dtr, di), dtype, fan_in=dtr),
        "dt_bias": jnp.full((di,), -4.6, dtype),   # softplus^-1(0.01)
        "A_log": jnp.log(A).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": _he(ks[4], (di, d), dtype, fan_in=di),
    }
    if cfg.ssm.inner_norms:
        p.update(dt_norm=jnp.ones((dtr,), dtype), B_norm=jnp.ones((N,), dtype),
                 C_norm=jnp.ones((N,), dtype))
    return p


def mamba_axes(cfg):
    a = {
        "in_proj": ("w_fsdp", "state"),
        "conv_w": ("state", None),
        "conv_b": ("state",),
        "x_proj": ("state", None),
        "dt_proj": (None, "state"),
        "dt_bias": ("state",),
        "A_log": ("state", None),
        "D": ("state",),
        "out_proj": ("state", "w_fsdp"),
    }
    if cfg.ssm.inner_norms:
        a.update(dt_norm=(None,), B_norm=(None,), C_norm=(None,))
    return a


def _discretize(params, x_conv, cfg, compute_dtype):
    """x_conv (B, C, di) -> (dt (B,C,di), B_ssm (B,C,N), C_ssm (B,C,N)) f32."""
    N = cfg.ssm.state_dim
    dtr = cfg.ssm.dt_rank or -(-cfg.d_model // 16)
    dbc = jnp.einsum("bsd,dk->bsk", x_conv.astype(compute_dtype),
                     params["x_proj"].astype(compute_dtype),
                     preferred_element_type=jnp.float32)
    dt_lr, B_ssm, C_ssm = jnp.split(dbc, [dtr, dtr + N], axis=-1)
    if cfg.ssm.inner_norms:
        dt_lr = rmsnorm({"scale": params["dt_norm"]}, dt_lr)
        B_ssm = rmsnorm({"scale": params["B_norm"]}, B_ssm)
        C_ssm = rmsnorm({"scale": params["C_norm"]}, C_ssm)
    dt = jnp.einsum("bsr,rd->bsd", dt_lr.astype(compute_dtype),
                    params["dt_proj"].astype(compute_dtype),
                    preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
    return dt, B_ssm, C_ssm


def mamba_apply(params, x, cfg, state=None, *, chunk=128,
                compute_dtype=jnp.bfloat16):
    """Full-sequence Mamba block.  x: (B, S, d).  state: optional carry-in
    {"h": (B, di, N), "conv": (B, K-1, di)}.  Returns (y, new_state)."""
    B, S, d = x.shape
    di = cfg.ssm.expand * d
    N = cfg.ssm.state_dim
    Kw = cfg.ssm.conv_dim
    if state is None:
        state = {"h": jnp.zeros((B, di, N), jnp.float32),
                 "conv": jnp.zeros((B, Kw - 1, di), jnp.float32)}

    with jax.named_scope("mamba.proj"):
        xz = jnp.einsum("bsd,de->bse", x.astype(compute_dtype),
                        params["in_proj"].astype(compute_dtype))
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = logical_constraint(x_in, ("batch", "seq", "state"))
    x_conv, conv_state = causal_conv1d(x_in, params["conv_w"], params["conv_b"],
                                       state["conv"])
    x_conv = jax.nn.silu(x_conv.astype(jnp.float32)).astype(compute_dtype)

    with jax.named_scope("mamba.proj"):
        dt, B_ssm, C_ssm = _discretize(params, x_conv, cfg, compute_dtype)
    with jax.named_scope("mamba.scan"):
        plan = _kernel_plan(dt.shape)
        if plan is None:
            y, h_last = _chunked_scan(params, dt, B_ssm, C_ssm, x_conv, z,
                                      state["h"], chunk)
        else:
            y, h_last = _kernel_scan(chunk, plan, params["A_log"],
                                     params["D"], dt, B_ssm, C_ssm, x_conv,
                                     z, state["h"])
    y = logical_constraint(y.astype(compute_dtype), ("batch", "seq", "state"))
    with jax.named_scope("mamba.proj"):
        out = jnp.einsum("bse,ed->bsd", y,
                         params["out_proj"].astype(compute_dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out = mem_write(out, "ssm_output", ("batch", "seq", "embed"))
    return out, {"h": h_last, "conv": conv_state}


def _chunked_scan(params, dt, B_ssm, C_ssm, x_conv, z, h0, chunk):
    """The selective scan over (B, S, di) inputs, ``chunk`` tokens at a
    time, with the D skip and the z gate: (y (B, S, di) f32, h_last)."""
    B, S, di = dt.shape
    A = -jnp.exp(params["A_log"].astype(jnp.float32))   # (di, N)
    xf = x_conv.astype(jnp.float32)

    n = S // min(chunk, S)
    c = S // n

    def body(h, xs):
        dt_c, B_c, C_c, x_c = xs    # (B,c,di), (B,c,N), (B,c,N), (B,c,di)
        dA = jnp.exp(dt_c[..., None] * A[None, None])            # (B,c,di,N)
        dBx = dt_c[..., None] * B_c[:, :, None, :] * x_c[..., None]

        def op(l, r):
            al, bl = l
            ar, br = r
            return al * ar, ar * bl + br

        acum, bcum = jax.lax.associative_scan(op, (dA, dBx), axis=1)
        h_t = acum * h[:, None] + bcum                            # (B,c,di,N)
        y_c = jnp.einsum("bcdn,bcn->bcd", h_t, C_c)
        return h_t[:, -1], y_c

    def split_chunks(t):
        return jnp.moveaxis(t.reshape(B, n, c, *t.shape[2:]), 1, 0)

    h_last, ys = jax.lax.scan(
        body, h0,
        (split_chunks(dt), split_chunks(B_ssm), split_chunks(C_ssm),
         split_chunks(xf)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, di)
    y = y + params["D"].astype(jnp.float32) * xf
    return y * jax.nn.silu(z.astype(jnp.float32)), h_last


def _kernel_plan(shape):
    """Where the Pallas scan runs for (B, S, di) inputs: on a TPU, with
    each shard's channels a whole number of 128 lanes and the sequence
    whole on every shard.  Returns the mesh (None without one) and the
    shard_map specs of (activations, B and C, channels, state), or None
    where the XLA scan runs."""
    if jax.default_backend() != "tpu":
        return None
    mesh = current_mesh()
    act = logical_to_pspec(("batch", "seq", "state"), mesh=mesh, shape=shape)
    axes = act[2] if isinstance(act[2], tuple) else (act[2],)
    shards = math.prod(mesh.shape[a] for a in axes if a is not None) \
        if mesh is not None else 1
    if act[1] is not None or (shape[2] // shards) % 128:
        return None
    return mesh, (act, P(act[0], None, None), P(act[2], None),
                  P(act[0], act[2], None))


def _kernel_call(plan, A_log, D, dt, B_ssm, C_ssm, x, z, h0):
    mesh, (act, bc, chan, st) = plan
    if mesh is None:
        return ops.selective_scan(dt, B_ssm, C_ssm, x, z, A_log, D, h0)
    # the channels are independent: each shard scans its own, with no
    # collective
    return jax.shard_map(
        ops.selective_scan, mesh=mesh,
        in_specs=(act, bc, bc, act, act, chan, P(chan[0]), st),
        out_specs=(act, st), check_vma=False)(
            dt, B_ssm, C_ssm, x, z, A_log, D, h0)


def _xla_scan(chunk, A_log, D, dt, B_ssm, C_ssm, x, z, h0):
    y, h_last = _chunked_scan({"A_log": A_log, "D": D}, dt, B_ssm, C_ssm,
                              x, z, h0, chunk)
    return y.astype(x.dtype), h_last


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_scan(chunk, plan, A_log, D, dt, B_ssm, C_ssm, x, z, h0):
    """The Pallas scan forward; its gradients are the XLA scan's."""
    return _kernel_call(plan, A_log, D, dt, B_ssm, C_ssm, x, z, h0)


def _kernel_scan_fwd(chunk, plan, *args):
    return _kernel_call(plan, *args), args


def _kernel_scan_bwd(chunk, plan, args, g):
    return jax.vjp(functools.partial(_xla_scan, chunk), *args)[1](g)


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def mamba_decode_step(params, x, cfg, state, *, compute_dtype=jnp.bfloat16):
    """Single-token decode.  x: (B, 1, d).  O(1) state update."""
    with jax.named_scope("mamba.proj"):
        xz = jnp.einsum("bsd,de->bse", x.astype(compute_dtype),
                        params["in_proj"].astype(compute_dtype))
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_conv, conv_state = causal_conv1d(x_in, params["conv_w"], params["conv_b"],
                                       state["conv"])
    x_conv = jax.nn.silu(x_conv.astype(jnp.float32)).astype(compute_dtype)
    with jax.named_scope("mamba.proj"):
        dt, B_ssm, C_ssm = _discretize(params, x_conv, cfg, compute_dtype)
    with jax.named_scope("mamba.scan"):
        A = -jnp.exp(params["A_log"].astype(jnp.float32))
        xf = x_conv.astype(jnp.float32)
        dA = jnp.exp(dt[:, 0, :, None] * A[None])                # (B,di,N)
        dBx = dt[:, 0, :, None] * B_ssm[:, 0, None, :] * xf[:, 0, :, None]
        h = dA * state["h"] + dBx
        y = jnp.einsum("bdn,bn->bd", h, C_ssm[:, 0])[:, None]
        y = y + params["D"].astype(jnp.float32) * xf
        y = y * jax.nn.silu(z.astype(jnp.float32))
    with jax.named_scope("mamba.proj"):
        out = jnp.einsum("bse,ed->bsd", y.astype(compute_dtype),
                         params["out_proj"].astype(compute_dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    return out, {"h": h, "conv": conv_state}
