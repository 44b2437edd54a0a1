"""Multi-device behaviour (8-device subprocess): P2P, multicast, sync,
socket virtualization, MoE mem-vs-mcast equivalence, gradient compression.
These are the framework-level reproductions of the paper's C1-C4."""

_CODE = r"""
import jax, jax.numpy as jnp, numpy as np, functools
from jax.sharding import PartitionSpec as P
from repro.core import p2p as P2P
from repro.core import multicast as MC
from repro.core import sync as SYNC
from repro.core import socket as SOCK
from repro.core.comm import CommMode, CommPlan, TransferDescriptor
from repro.core.socket import StageRegistry, AcceleratorSocket
from repro.optim.compression import compressed_psum

mesh = jax.make_mesh((8,), ("s",), axis_types=(jax.sharding.AxisType.Auto,))
smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)

# ---- C1: pull-based P2P ring shift --------------------------------------
x = jnp.arange(8.0)[:, None] * jnp.ones((1, 4))
shifted = jax.jit(smap(lambda v: P2P.p2p_shift(v, "s", 1),
                       in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(shifted[:, 0], np.roll(np.arange(8.0), 1))
print("P2P_SHIFT_OK", flush=True)

# ---- C1: flexible burst re-blocking across a P2P transfer ---------------
x8 = jnp.arange(8.0)[:, None] * jnp.ones((1, 8))   # 8 words per shard
y = jax.jit(smap(lambda v: P2P.p2p_reblocked(v, "s", src=2, dst=5,
                                             producer_burst=4,
                                             consumer_burst=8),
                 in_specs=P("s", None), out_specs=P("s", None)))(x8)
got = np.asarray(y).reshape(8, -1)[5]          # consumer rank 5's words
np.testing.assert_allclose(got, np.full(8, 2.0))
print("P2P_REBLOCK_OK", flush=True)

# ---- C2: multicast broadcast + subset ------------------------------------
b = jax.jit(smap(lambda v: MC.multicast_bcast(v, "s", src=3),
                 in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(np.asarray(b), 3.0)
sub = jax.jit(smap(lambda v: MC.multicast_subset(v, "s", 1, [2, 5, 6]),
                   in_specs=P("s", None), out_specs=P("s", None)))(x)
sub = np.asarray(sub)
for r in (2, 5, 6):
    np.testing.assert_allclose(sub[r], 1.0)
for r in (0, 3, 4, 7):
    np.testing.assert_allclose(sub[r], 0.0)
np.testing.assert_allclose(sub[1], 1.0)        # source keeps its data
print("MCAST_OK", flush=True)

# ---- C3: sync region ------------------------------------------------------
flags = jax.jit(smap(lambda v: SYNC.barrier("s") * jnp.ones_like(v),
                     in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(np.asarray(flags), 8.0)
ready = jax.jit(smap(
    lambda v: SYNC.ready_check(jnp.ones((), jnp.int32), "s")[None],
    in_specs=P("s", None), out_specs=P("s")))(x)
assert bool(np.all(ready))
print("SYNC_OK", flush=True)

# ---- C4: socket with virtualized peers, descriptor-based API --------------
reg = StageRegistry("s", {"producer": 1, "consumer": 6})
sock = AcceleratorSocket(reg)
desc = TransferDescriptor("stage_activation", axes=("batch", None),
                          source="producer", consumer="consumer", pull=True)
out = jax.jit(smap(lambda v: sock.read(v, desc),
                   in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(np.asarray(out).reshape(8, -1)[6], 1.0)
# retarget the producer through the LUT — no code change (static path:
# the perm is baked, so a fresh jit re-resolves the LUT)
reg.remap("producer", 4)
out2 = jax.jit(smap(lambda v: sock.read(v, desc),
                    in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(np.asarray(out2).reshape(8, -1)[6], 4.0)
rec = [r for r in SOCK.issued_records() if r.name == "stage_activation"][-1]
assert rec.issued == "P2P" and rec.user == 1, rec   # virtual LUT index
print("SOCKET_OK", flush=True)

# ---- C4/C5: remap followed WITHOUT retracing (dynamic LUT path) -----------
reg2 = StageRegistry("s", {"producer": 1, "consumer": 6})
sock2 = AcceleratorSocket(reg2)
traces = []

def stage(v, src):
    traces.append(1)
    return sock2.read(v, desc, source=src, consumer=6)

fn = jax.jit(smap(stage, in_specs=(P("s", None), P()),
                  out_specs=P("s", None)))
o1 = fn(x, sock2.peer_rank("producer"))
np.testing.assert_allclose(np.asarray(o1).reshape(8, -1)[6], 1.0)
reg2.remap("producer", 4)
o2 = fn(x, sock2.peer_rank("producer"))
np.testing.assert_allclose(np.asarray(o2).reshape(8, -1)[6], 4.0)
assert len(traces) == 1, f"stage fn retraced {len(traces)}x after remap"
print("SOCKET_REMAP_NO_RETRACE_OK", flush=True)

# ---- C2/C4: plan-driven descriptor write (multicast + sync fence) ---------
reg3 = StageRegistry("s", {"p": 3, "c1": 2, "c2": 5, "c3": 6})
plan = CommPlan({"kv_prefix": CommMode.MCAST})
sock3 = AcceleratorSocket(reg3, plan)
wdesc = TransferDescriptor("kv_prefix", source="p", dests=("c1", "c2", "c3"),
                           sync=True)
wout = jax.jit(smap(lambda v: sock3.write(v, wdesc),
                    in_specs=P("s", None), out_specs=P("s", None)))(x)
wout = np.asarray(wout)
for r in (2, 5, 6):
    np.testing.assert_allclose(wout[r], 3.0)   # src rank 3's payload
for r in (0, 1, 4, 7):
    np.testing.assert_allclose(wout[r], 0.0)   # non-members get zeros
np.testing.assert_allclose(wout[3], 3.0)       # source keeps its data
rec = [r for r in SOCK.issued_records() if r.name == "kv_prefix"][-1]
assert rec.issued == "MCAST" and rec.user == 3 and rec.sync, rec
print("SOCKET_WRITE_OK", flush=True)

# ---- C4/C5: serve-engine kv-prefix hand-off, consumer migration without ---
# ---- retracing (mirrors ServeEngine.make_stage_kv_writer)                ---
reg4 = StageRegistry("s", {"prefill": 0, "d1": 1, "d2": 2, "d3": 3})
sock4 = AcceleratorSocket(reg4, CommPlan({"kv_prefix": CommMode.MCAST}))
kvdesc = TransferDescriptor("kv_prefix", source="prefill",
                            dests=("d1", "d2", "d3"), sync=True)
xp = (jnp.arange(8.0)[:, None] + 1.0) * jnp.ones((1, 4))  # rank r holds r+1
ktraces = []

def kv_burst(v, ranks):
    ktraces.append(1)
    # traced dests vector = the engine's consumer_ranks(): the dynamic-LUT
    # multicast follows a later remap without retracing
    return sock4.write(v, kvdesc, producer=0, dests=list(ranks))

kv_fn = jax.jit(smap(kv_burst, in_specs=(P("s", None), P()),
                     out_specs=P("s", None)))
cranks = lambda: jnp.asarray(
    [reg4.rank_of(n) for n in ("d1", "d2", "d3")], jnp.int32)
k1 = np.asarray(kv_fn(xp, cranks()))
for r in (1, 2, 3):
    np.testing.assert_allclose(k1[r], 1.0)     # prefill rank 0's payload
for r in (4, 5, 6, 7):
    np.testing.assert_allclose(k1[r], 0.0)
reg4.remap("d3", 6)                            # migrate a decode consumer
k2 = np.asarray(kv_fn(xp, cranks()))
for r in (1, 2, 6):
    np.testing.assert_allclose(k2[r], 1.0)
np.testing.assert_allclose(k2[3], 0.0)         # the old rank dropped out
assert len(ktraces) == 1, f"kv writer retraced {len(ktraces)}x after remap"
rec = [r for r in SOCK.issued_records() if r.name == "kv_prefix"][-1]
assert rec.issued == "MCAST" and rec.user == 3 and \
    rec.impl == "dynamic_lut", rec
print("ENGINE_KV_REMAP_OK", flush=True)

# ---- C4: a MEM verdict is an accounting choice, not a dropped transfer ----
SOCK.reset_issue_log()   # judge only this section's records against memplan
memplan = CommPlan({"stage_activation": CommMode.MEM,
                    "moe_dispatch": CommMode.MEM})
sockm = AcceleratorSocket(None, memplan, axis_name="s")
fwd = jax.jit(smap(lambda v: sockm.forward_to_next(v),
                   in_specs=P("s", None), out_specs=P("s", None)))(x)
np.testing.assert_allclose(np.asarray(fwd)[:, 0],
                           np.roll(np.arange(8.0), 1))   # still shifts
rec = [r for r in SOCK.issued_records() if r.name == "stage_activation"][-1]
assert rec.issued == "MEM" and rec.user == 0 and \
    rec.impl == "mem_roundtrip", rec
xe = jnp.arange(64.0).reshape(8, 8)
ex = jax.jit(smap(lambda v: sockm.exchange(
    v.reshape(8, 1), TransferDescriptor("moe_dispatch"), split_axis=0,
    concat_axis=0).reshape(1, 8),
    in_specs=P("s", None), out_specs=P("s", None)))(xe)
np.testing.assert_allclose(np.asarray(ex), np.asarray(xe).T)  # delivered
rec = [r for r in SOCK.issued_records() if r.name == "moe_dispatch"][-1]
assert rec.issued == "MEM" and rec.user == 0, rec
assert SOCK.issued_matches_plan(memplan)
print("SOCKET_MEM_VERDICT_OK", flush=True)

# ---- C2/C5: Pallas multicast-stream fast path through the socket ----------
from repro.kernels import ops
regk = StageRegistry("s", {"p": 3, **{f"c{i}": i for i in range(8) if i != 3}})
sockk = AcceleratorSocket(regk, use_kernels=True,
                          interpret=ops.interpret_params())
kdesc = TransferDescriptor("kv_prefix", source="p",
                           dests=tuple(f"c{i}" for i in range(8) if i != 3))
xm = jax.random.normal(jax.random.key(7), (16, 32), jnp.float32)
kout = jax.jit(smap(lambda v: sockk.write(v, kdesc),
                    in_specs=P(None, None), out_specs=P("s", None)))(xm)
np.testing.assert_allclose(np.asarray(kout), np.tile(np.asarray(xm), (8, 1)),
                           rtol=1e-6, atol=1e-6)
rec = [r for r in SOCK.issued_records() if r.name == "kv_prefix"][-1]
assert rec.impl == "mcast_stream_kernel", rec
print("SOCKET_KERNEL_OK", flush=True)

# ---- C2/C4: MoE mem (shared-memory) == mcast (multicast) ------------------
from repro.configs import get_reduced
from repro.models import moe as M
import dataclasses
cfg = get_reduced("dbrx-132b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, n_experts=8, capacity_factor=16.0))  # no drops => equal
params = M.moe_init(jax.random.key(0), cfg)
B, S, d = 2, 16, cfg.d_model
xx = jax.random.normal(jax.random.key(1), (B, S, d), jnp.float32)

# expert weights are sharded over the axis (the socket's expert placement);
# the router is replicated
pspec = {"router": P(), "w_gate": P("s", None, None),
         "w_up": P("s", None, None), "w_down": P("s", None, None)}
mem_fn = jax.jit(smap(
    lambda p, v: M.moe_apply(p, v, cfg, mode="mem", model_axis="s")[0],
    in_specs=(pspec, P(None, None, None)), out_specs=P(None, None, None)))
mc_fn = jax.jit(smap(
    lambda p, v: M.moe_apply(p, v, cfg, mode="mcast", model_axis="s")[0],
    in_specs=(pspec, P(None, "s", None)), out_specs=P(None, "s", None)))
y_mem = mem_fn(params, xx)
y_mc = mc_fn(params, xx)
np.testing.assert_allclose(np.asarray(y_mem), np.asarray(y_mc),
                           rtol=5e-2, atol=5e-2)
# both dispatch paths issued through the socket: the mcast trace recorded
# the two all_to_all exchanges, the mem trace the pinned-MEM combine psum
moe_sites = {r.site: r.issued for r in SOCK.issued_records()}
assert moe_sites.get("moe.dispatch") == "MCAST", moe_sites
assert moe_sites.get("moe.combine") == "MCAST", moe_sites
assert moe_sites.get("moe.combine_psum") == "MEM", moe_sites
print("MOE_MODES_OK", flush=True)

# ---- compression: int8 EF psum ≈ f32 psum ---------------------------------
SOCK.reset_issue_log()
g = jax.random.normal(jax.random.key(2), (8, 64))
mean_true = np.mean(np.asarray(g), axis=0)
comp_fn = jax.jit(smap(
    lambda v: compressed_psum(v[0], "s")[0][None],
    in_specs=P("s", None), out_specs=P(None, None)))
mean_q = np.asarray(comp_fn(g))[0]
err = np.max(np.abs(mean_q - mean_true))
scale = np.max(np.abs(np.asarray(g))) / 127.0
assert err <= scale + 1e-6, (err, scale)
# the int32 combine is a real socket issue priced at the int8 wire bytes
# (one byte per element of the per-shard payload), not the widened sum
crec = [r for r in SOCK.issued_records()
        if r.site == "compression.grad_reduce_compressed"][-1]
assert crec.channel == "reduce" and crec.issued == "MEM", crec
assert crec.nbytes == 64, crec.nbytes   # (1, 64) shard -> 64 wire bytes
print("COMPRESSION_OK", flush=True)
"""


def test_distributed_battery(subproc):
    out = subproc(_CODE, n_devices=8)
    for marker in ("P2P_SHIFT_OK", "P2P_REBLOCK_OK", "MCAST_OK", "SYNC_OK",
                   "SOCKET_OK", "SOCKET_REMAP_NO_RETRACE_OK",
                   "SOCKET_WRITE_OK", "ENGINE_KV_REMAP_OK",
                   "SOCKET_MEM_VERDICT_OK",
                   "SOCKET_KERNEL_OK", "MOE_MODES_OK", "COMPRESSION_OK"):
        assert marker in out, out
