"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in TPU interpret
mode.  Multi-device kernels run in an 8-device subprocess (device count is
locked at first jax init in the main process)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ------------------------------------------------- single-device kernels ----

@pytest.mark.parametrize("shape", [(16, 128), (64, 128), (32, 256),
                                   (13, 128), (50, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_blocks", [2, 4])
def test_dma_double_buffer_sweep(shape, dtype, n_blocks):
    # (13, .) / (50, .): rows do NOT divide n_blocks — the final block
    # clamps its DMA window and rewrites a few trailing rows (elementwise
    # op, so the re-written values are identical)
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    y = ops.dma_stream(x, 1.3, n_blocks=n_blocks,
                       interpret=ops.interpret_params())
    expect = ref.dma_stream_ref(x, 1.3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m", [16, 13])   # 13: uneven final block (clamped)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streamed_gather_matmul_bitwise(m, dtype):
    """The double-buffered streamed weights-gather matmul is BIT-identical
    to the unfused reference (``jnp.dot`` at f32 accumulate) — row-blocking
    the streamed operand keeps every output row's contraction intact, so
    the socket's streamed-MEM rung and its serial fallback cannot drift."""
    from repro.kernels.streamed_gather import streamed_gather_matmul
    x = jax.random.normal(jax.random.key(0), (m, 32), dtype)
    w = jax.random.normal(jax.random.key(1), (32, 8), dtype)
    y = streamed_gather_matmul(x, w, n_blocks=4,
                               interpret=ops.interpret_params())
    expect = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
        jnp.promote_types(x.dtype, w.dtype))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(expect, np.float32))


# ------------------------------------------------ multi-device (subproc) ----

_SWEEP_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ref

mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
ip = ops.interpret_params()
P = 8

for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 5e-2)):
    m, k, n = 8, 16, 8
    xs = jax.random.normal(jax.random.key(0), (P * m, k), dtype)
    w = jax.random.normal(jax.random.key(1), (k, n), dtype)
    out = ops.allgather_matmul(xs, w, mesh, "x", interpret=ip)
    expect = ref.allgather_matmul_ref(xs.reshape(P, m, k), w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol * 10)
print("AG_OK", flush=True)

x = jax.random.normal(jax.random.key(2), (16, 32), jnp.float32)
w = jax.random.normal(jax.random.key(3), (32, 8), jnp.float32)
out = ops.reducescatter_matmul(x, w, mesh, "x", interpret=ip)
np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w, np.float32),
                           rtol=1e-3, atol=1e-3)
print("RS_OK", flush=True)

for src, n_chunks in ((0, 4), (3, 2)):
    xm = jax.random.normal(jax.random.key(src), (16, 32), jnp.float32)
    outm = ops.multicast(xm, mesh, "x", src=src, n_chunks=n_chunks,
                         interpret=ip)
    np.testing.assert_allclose(outm, jnp.tile(xm, (8, 1)),
                               rtol=1e-6, atol=1e-6)
print("MCAST_OK", flush=True)
"""


def test_collective_kernel_sweep(subproc):
    out = subproc(_SWEEP_CODE, n_devices=8)
    assert "AG_OK" in out and "RS_OK" in out and "MCAST_OK" in out


# ------------------------------------------ ring kernels vs lax reference ----

_RING_EQUIV_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels import ops

mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
ip = ops.interpret_params()
Pn = 8

def lax_ag_mm(x, w):
    def body(xs, ws):
        full = jax.lax.all_gather(xs, "x", axis=0, tiled=True)
        return jnp.dot(full, ws, preferred_element_type=jnp.float32
                       ).astype(jnp.promote_types(xs.dtype, ws.dtype))
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("x", None), P(None, None)),
                                 out_specs=P(None, None),
                                 check_vma=False))(x, w)

def lax_rs_mm(x, w):
    def body(xs, ws):
        part = jnp.dot(xs, ws, preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(part, "x", scatter_dimension=0,
                                    tiled=True)
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(None, "x"), P("x", None)),
                                 out_specs=P("x", None),
                                 check_vma=False))(x, w)

# all-gather matmul: 2 dtypes x uneven per-rank chunk counts (m = 3 rows
# per rank is NOT a power of two; m = 8 is the friendly case)
for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 5e-2)):
    for m in (3, 8):
        k, n = 16, 8
        x = jax.random.normal(jax.random.key(m), (Pn * m, k), dtype)
        w = jax.random.normal(jax.random.key(m + 1), (k, n), dtype)
        fused = ops.allgather_matmul(x, w, mesh, "x", interpret=ip)
        ref = lax_ag_mm(x, w)
        np.testing.assert_allclose(np.asarray(fused, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol * 10,
                                   err_msg=f"ag dtype={dtype} m={m}")
print("RING_AG_EQUIV_OK", flush=True)

# reduce-scatter matmul: 2 dtypes x uneven output chunks (m = 24 -> 3
# rows per rank; m = 16 -> 2)
for dtype, tol in ((jnp.float32, 1e-3), (jnp.bfloat16, 5e-2)):
    for m in (16, 24):
        x = jax.random.normal(jax.random.key(m), (m, 32), dtype)
        w = jax.random.normal(jax.random.key(m + 3), (32, 8), dtype)
        fused = ops.reducescatter_matmul(x, w, mesh, "x", interpret=ip)
        ref = lax_rs_mm(x, w)
        np.testing.assert_allclose(np.asarray(fused, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol * 10,
                                   err_msg=f"rs dtype={dtype} m={m}")
print("RING_RS_EQUIV_OK", flush=True)
"""


def test_ring_kernels_match_unfused_lax(subproc):
    """Interpret-mode equivalence of the fused ring kernels against the
    unfused lax lowering (all_gather+dot / dot+psum_scatter) across two
    dtypes and uneven chunk counts — the numerical contract behind the
    socket's FUSED_RING dispatch."""
    out = subproc(_RING_EQUIV_CODE, n_devices=8)
    assert "RING_AG_EQUIV_OK" in out and "RING_RS_EQUIV_OK" in out


# -------------------------------------------- socket FUSED_RING dispatch ----

_FUSED_DISPATCH_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels.ops import interpret_params
from repro.core.comm import (CommMode, CommPlan, TransferDescriptor,
                             register_fusion_target)
from repro.core import socket as SOCK

mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
ip = interpret_params()
plan = CommPlan({"weights": CommMode.P2P, "grad_scatter": CommMode.P2P})
# this subprocess never imports repro.models.layers, so the consumer-matmul
# labels the descriptors fuse with must be registered here (the socket
# rejects a dangling fused_with at issue time)
register_fusion_target("mlp.up_proj")
register_fusion_target("mlp.down_proj")
gdesc = TransferDescriptor("weights", fused_with="mlp.up_proj",
                           site="t.gather")
rdesc = TransferDescriptor("grad_scatter", fused_with="mlp.down_proj",
                           site="t.rs")

x = jax.random.normal(jax.random.key(0), (8 * 4, 16), jnp.float32)
w = jax.random.normal(jax.random.key(1), (16, 8), jnp.float32)

def run_gather(use_kernels, p=plan):
    def body(xs, ws):
        s = SOCK.socket_for_axis("x", p, use_kernels=use_kernels,
                                 interpret=ip)
        return s.gather_matmul(xs, ws, gdesc)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)

SOCK.reset_issue_log()
fused = run_gather(True)
rec = SOCK.issued_records()[-1]
assert rec.fused and rec.impl == "ring_allgather_matmul", rec
assert rec.channel == "gather_matmul" and rec.issued == "P2P"
assert rec.user == 1   # ring hop = unicast write (the user=1 degeneracy)
SOCK.reset_issue_log()
unfused = run_gather(False)
rec = SOCK.issued_records()[-1]
assert not rec.fused and rec.impl == "lax_all_gather", rec
np.testing.assert_allclose(np.asarray(fused), np.asarray(x @ w),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(unfused), np.asarray(x @ w),
                           rtol=1e-4, atol=1e-4)
assert SOCK.issued_matches_plan(plan)

# a MEM verdict falls back serially and is charged the round-trip
SOCK.reset_issue_log()
memp = CommPlan({"weights": CommMode.MEM})
out_mem = run_gather(True, memp)
rec = SOCK.issued_records()[-1]
assert rec.issued == "MEM" and not rec.fused and rec.user == 0
np.testing.assert_allclose(np.asarray(out_mem), np.asarray(x @ w),
                           rtol=1e-4, atol=1e-4)
print("FUSED_GM_OK", flush=True)

xr = jax.random.normal(jax.random.key(2), (16, 8 * 4), jnp.float32)
wr = jax.random.normal(jax.random.key(3), (8 * 4, 8), jnp.float32)

def run_rs(use_kernels):
    def body(xs, ws):
        s = SOCK.socket_for_axis("x", plan, use_kernels=use_kernels,
                                 interpret=ip)
        return s.matmul_reduce_scatter(xs, ws, rdesc)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
        out_specs=P("x", None), check_vma=False))(xr, wr)

SOCK.reset_issue_log()
f = run_rs(True)
rec = SOCK.issued_records()[-1]
assert rec.fused and rec.impl == "ring_reducescatter_matmul", rec
u = run_rs(False)
rec = SOCK.issued_records()[-1]
assert not rec.fused and rec.impl == "lax_psum_scatter", rec
np.testing.assert_allclose(np.asarray(f), np.asarray(xr @ wr),
                           rtol=1e-3, atol=1e-3)
np.testing.assert_allclose(np.asarray(u), np.asarray(xr @ wr),
                           rtol=1e-3, atol=1e-3)
print("FUSED_RS_OK", flush=True)

# the migrated attention o-projection site: head-sharded context x
# row-sharded w_o combined by the fused ring, output sequence-sharded
from repro.models.attention import o_proj_tp

ctx = jax.random.normal(jax.random.key(4), (16, 8 * 4), jnp.float32)
w_o = jax.random.normal(jax.random.key(5), (8 * 4, 8), jnp.float32)

def run_oproj(use_kernels):
    def body(cs, ws):
        s = SOCK.socket_for_axis("x", plan, use_kernels=use_kernels,
                                 interpret=ip)
        return o_proj_tp(cs, ws, socket=s)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
        out_specs=P("x", None), check_vma=False))(ctx, w_o)

SOCK.reset_issue_log()
of = run_oproj(True)
rec = SOCK.issued_records()[-1]
assert rec.site == "attn.o_proj" and rec.fused, rec
ou = run_oproj(False)
np.testing.assert_allclose(np.asarray(of), np.asarray(ctx @ w_o),
                           rtol=1e-3, atol=1e-3)
np.testing.assert_allclose(np.asarray(ou), np.asarray(ctx @ w_o),
                           rtol=1e-3, atol=1e-3)
print("FUSED_OPROJ_OK", flush=True)
"""


def test_socket_fused_ring_dispatch(subproc):
    """The FUSED_RING outcome end-to-end: a P2P verdict + declared
    consumer matmul + use_kernels dispatches the ring kernels (IssueRecord
    marked fused, user=1 ring-hop encoding), the lax fallback and the MEM
    round-trip produce identical numbers, and every issue conforms to the
    plan."""
    out = subproc(_FUSED_DISPATCH_CODE, n_devices=8)
    assert "FUSED_GM_OK" in out and "FUSED_RS_OK" in out
    assert "FUSED_OPROJ_OK" in out


_FFN_TP_CODE = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.core import socket as SOCK
from repro.core.sharding import use_rules, DEFAULT_RULES
from repro.models import transformer as T

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_reduced("qwen3-4b")
B, S = 4, 32
flags0 = T.RunFlags(distributed=True, remat="none")
flags1 = dataclasses.replace(flags0, ffn_tp=True)
params = T.init_params(jax.random.key(0), cfg, jnp.float32)
batch = {"tokens": jnp.full((B, S), 3, jnp.int32),
         "labels": jnp.ones((B, S), jnp.int32)}

def loss(flags):
    with use_rules(dict(DEFAULT_RULES), mesh):
        return jax.jit(lambda p, b: T.forward_train(p, b, cfg, flags))(
            params, batch)

l0 = float(loss(flags0))
SOCK.reset_issue_log()
l1 = float(loss(flags1))
np.testing.assert_allclose(l0, l1, rtol=2e-2)
sites = {r.site for r in SOCK.issued_records()}
assert "mlp.up_gather" in sites and "mlp.down_proj" in sites, sites
print("FFN_TP_OK", flush=True)
"""


def test_transformer_ffn_tp_matches_gspmd(subproc):
    """The migrated dense-MLP blocks (socket-issued fused transfers inside
    shard_map) reproduce the GSPMD baseline loss, and both fused call
    sites appear in the issue log."""
    out = subproc(_FFN_TP_CODE, n_devices=8)
    assert "FFN_TP_OK" in out


# ---------------------- streamed-MEM gather + fused MoE dispatch chain ------

_STREAMED_AND_CHAIN_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels.ops import interpret_params
from repro.core.comm import CommMode, CommPlan, TransferDescriptor
from repro.core import socket as SOCK

mesh = jax.make_mesh((8,), ("s",), axis_types=(jax.sharding.AxisType.Auto,))
ip = interpret_params()

# ---- streamed-MEM weights gather: plan.streamed drives the DMA schedule ---
from repro.models.layers import MLP_GATHER_DESC

splan = CommPlan({"weights": CommMode.MEM},
                 streamed_names=frozenset({"weights"}))
x = jax.random.normal(jax.random.key(0), (8 * 4, 16), jnp.float32)
w = jax.random.normal(jax.random.key(1), (16, 8), jnp.float32)

def run_gather(use_kernels, plan):
    def body(xs, ws):
        s = SOCK.socket_for_axis("s", plan, use_kernels=use_kernels,
                                 interpret=ip)
        return s.gather_matmul(xs, ws, MLP_GATHER_DESC)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("s", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)

SOCK.reset_issue_log()
streamed = run_gather(True, splan)
rec = SOCK.issued_records()[-1]
assert rec.fused and rec.impl == "streamed_gather_matmul", rec
assert rec.issued == "MEM" and rec.user == 0, rec
serial = run_gather(False, splan)
rec = SOCK.issued_records()[-1]
assert not rec.fused and rec.impl == "mem_roundtrip", rec
# bit-identical: the streamed schedule only reorders HBM reads
np.testing.assert_array_equal(np.asarray(streamed), np.asarray(serial))
assert SOCK.issued_matches_plan(splan)
# a plain (non-streamed) MEM verdict never dispatches the stream, kernels
# on or not: streaming is an attribute of the PRICED decision
plain = CommPlan({"weights": CommMode.MEM})
run_gather(True, plain)
rec = SOCK.issued_records()[-1]
assert rec.impl == "mem_roundtrip" and not rec.fused, rec
print("STREAMED_GM_OK", flush=True)

# ---- fused MoE chain: dispatch -> expert FFN -> combine -------------------
import dataclasses
from repro.configs import get_reduced
from repro.models import moe as M

cfg = get_reduced("dbrx-132b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, n_experts=8, capacity_factor=16.0))
params = M.moe_init(jax.random.key(0), cfg)
B, S, d = 2, 16, cfg.d_model
xx = jax.random.normal(jax.random.key(1), (B, S, d), jnp.float32)
pspec = {"router": P(), "w_gate": P("s", None, None),
         "w_up": P("s", None, None), "w_down": P("s", None, None)}

def run_moe(use_kernels):
    def body(p, v):
        return M.moe_apply(p, v, cfg, mode="mcast", model_axis="s",
                           use_kernels=use_kernels, interpret=ip)[0]
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(pspec, P(None, "s", None)),
        out_specs=P(None, "s", None), check_vma=False))(params, xx)

SOCK.reset_issue_log()
y_fused = run_moe(True)
by_site = {r.site: r for r in SOCK.issued_records()}
drec, crec = by_site["moe.dispatch"], by_site["moe.combine"]
assert drec.fused and drec.impl == "ring_dispatch_ffn", drec
assert drec.channel == "dispatch_chain" and drec.issued == "MCAST", drec
assert crec.fused and crec.impl == "ring_dispatch_ffn", crec
SOCK.reset_issue_log()
y_serial = run_moe(False)
by_site = {r.site: r for r in SOCK.issued_records()}
assert not by_site["moe.dispatch"].fused, by_site
# the ring pipeline's per-slab FFN is bit-identical to the full-batch FFN
# of the serial all_to_all pair (row-independent expert einsums)
np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_serial))
print("MOE_CHAIN_OK", flush=True)
"""


def test_streamed_gather_and_moe_chain_dispatch(subproc):
    """The two new fused paths end-to-end through the socket: a streamed
    MEM verdict (``CommPlan.streamed_names``) dispatches the double-buffered
    gather kernel, and the mcast MoE dispatch->FFN->combine chain dispatches
    the ring pipeline — each bit-identical to its unfused fallback, each
    leaving the right IssueRecord."""
    out = subproc(_STREAMED_AND_CHAIN_CODE, n_devices=8)
    assert "STREAMED_GM_OK" in out and "MOE_CHAIN_OK" in out
