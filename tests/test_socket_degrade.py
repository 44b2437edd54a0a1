"""Socket robustness: bounded retry, the typed degradation ladder, and
the sync-fence stall watchdog.

A socket without a :class:`RetryPolicy` must behave exactly as before the
ladder existed (nothing caught); with one bound, a flaky kernel rung
retries with backoff, downgrades with a machine-readable
``degraded_reason``, and only a fully exhausted ladder raises
:class:`~repro.core.comm.FaultError` — the fault-tolerant runner's
recovery signal, re-exported unchanged from ``runtime.fault``."""

import time

import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import socket as SOCK
from repro.core.comm import CommMode, FaultError, TransferDescriptor
from repro.core.socket import (AcceleratorSocket, IssueRecord, RetryPolicy,
                               DEGRADATION_LADDER)

DESC = TransferDescriptor("weights", site="t.degrade")


def _policy(**kw):
    sleeps = []
    kw.setdefault("backoff_s", 0.01)
    pol = RetryPolicy(sleep=sleeps.append, **kw)
    return pol, sleeps


# ------------------------------------------------------------ RetryPolicy ----

def test_schedule_is_capped_geometric():
    pol = RetryPolicy(max_attempts=4, backoff_s=0.1, multiplier=2.0,
                      max_backoff_s=0.3)
    assert list(pol.schedule()) == pytest.approx([0.1, 0.2, 0.3])
    assert list(RetryPolicy(max_attempts=1).schedule()) == []
    assert RetryPolicy().sleep is time.sleep   # wall clock by default


def test_no_policy_never_catches():
    sock = AcceleratorSocket()
    with pytest.raises(ZeroDivisionError):
        sock._attempt(lambda: 1 // 0)


def test_flaky_rung_retries_then_succeeds():
    pol, sleeps = _policy(max_attempts=3)
    sock = AcceleratorSocket(retry=pol)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("link glitch")
        return 7

    assert sock._attempt(flaky) == (True, 7)
    assert calls["n"] == 3
    assert sleeps == pytest.approx(list(pol.schedule()))


def test_exhausted_rung_reports_attempts_and_error():
    pol, _ = _policy(max_attempts=2)
    sock = AcceleratorSocket(retry=pol)
    ok, (attempts, err) = sock._attempt(lambda: 1 // 0)
    assert not ok and attempts == 2
    assert isinstance(err, ZeroDivisionError)


def test_faulterror_is_never_retried():
    pol, sleeps = _policy(max_attempts=5)
    sock = AcceleratorSocket(retry=pol)

    def fatal():
        raise FaultError("watchdog fired inside the rung")

    with pytest.raises(FaultError):
        sock._attempt(fatal)
    assert sleeps == []   # no retry, no backoff


@pytest.mark.tier2
@settings(deadline=None, max_examples=30)
@given(attempts=st.integers(1, 8),
       backoff=st.floats(0.001, 0.5),
       mult=st.floats(1.0, 4.0),
       cap=st.floats(0.001, 1.0))
def test_schedule_properties(attempts, backoff, mult, cap):
    """len == max_attempts - 1; every delay positive and capped; the
    first delay is the base backoff (capped)."""
    pol = RetryPolicy(max_attempts=attempts, backoff_s=backoff,
                      multiplier=mult, max_backoff_s=cap)
    sched = list(pol.schedule())
    assert len(sched) == attempts - 1
    assert all(0 < d <= cap for d in sched)
    if sched:
        assert sched[0] == pytest.approx(min(backoff, cap))


# ------------------------------------------------------- degradation ladder ----

def _rungs(fail_first_n, results=("kern", "serial", "mem")):
    """Three ladder rungs where the first ``fail_first_n`` always raise."""
    def make(i, val):
        def thunk():
            if i < fail_first_n:
                raise RuntimeError(f"rung {i} down")
            return val
        return thunk
    issued = (CommMode.MCAST, CommMode.MCAST, CommMode.MEM)
    users = (3, 3, 0)
    impls = ("mcast_stream_kernel", "fork_tree", "mem_roundtrip")
    return [(DEGRADATION_LADDER[i], issued[i], users[i], impls[i], i == 0,
             make(i, results[i])) for i in range(3)]


def test_ladder_first_rung_success_logs_fused_undegraded():
    SOCK.reset_issue_log()
    pol, _ = _policy(max_attempts=1)
    sock = AcceleratorSocket(retry=pol)
    out = sock._ladder(DESC, "write", CommMode.MCAST, 128, _rungs(0))
    assert out == "kern"
    rec = SOCK.issued_records()[-1]
    assert rec.fused and rec.impl == "mcast_stream_kernel"
    assert rec.degraded_reason is None


def test_ladder_downgrade_carries_machine_readable_reason():
    SOCK.reset_issue_log()
    pol, _ = _policy(max_attempts=2)
    sock = AcceleratorSocket(retry=pol)
    out = sock._ladder(DESC, "write", CommMode.MCAST, 128, _rungs(1))
    assert out == "serial"
    rec = SOCK.issued_records()[-1]
    assert rec.impl == "fork_tree" and not rec.fused
    assert rec.issued == "MCAST"
    assert "ladder FUSED_RING->P2P" in rec.degraded_reason
    assert "2 attempt(s)" in rec.degraded_reason
    assert "RuntimeError" in rec.degraded_reason


def test_ladder_mem_rung_accumulates_both_hops():
    SOCK.reset_issue_log()
    pol, _ = _policy(max_attempts=1)
    sock = AcceleratorSocket(retry=pol)
    out = sock._ladder(DESC, "write", CommMode.MCAST, 128, _rungs(2))
    assert out == "mem"
    rec = SOCK.issued_records()[-1]
    assert rec.issued == "MEM" and rec.user == 0
    assert "ladder FUSED_RING->P2P" in rec.degraded_reason
    assert "ladder P2P->MEM" in rec.degraded_reason


def test_ladder_exhausted_raises_faulterror():
    SOCK.reset_issue_log()
    pol, _ = _policy(max_attempts=2)
    sock = AcceleratorSocket(retry=pol)
    with pytest.raises(FaultError, match="ladder exhausted at rung MEM"):
        sock._ladder(DESC, "write", CommMode.MCAST, 128, _rungs(3))
    # nothing was logged: the dispatch never completed
    assert SOCK.issued_records() == []


# ----------------------------------------------------- fence stall watchdog ----

def test_fence_watchdog_turns_stall_into_faulterror(monkeypatch):
    monkeypatch.setattr(SOCK.SYNC, "barrier",
                        lambda axis: time.sleep(30))
    sock = AcceleratorSocket(axis_name="x", fence_timeout_s=0.05)
    with pytest.raises(FaultError, match="stalled past"):
        sock._fence(jnp.ones((2,)), CommMode.P2P)


def test_fence_watchdog_passes_through_fast_barriers(monkeypatch):
    flags = []
    monkeypatch.setattr(SOCK.SYNC, "barrier", lambda axis: "FLAG")
    monkeypatch.setattr(SOCK.SYNC, "ordered_after",
                        lambda x, flag: flags.append(flag) or x)
    sock = AcceleratorSocket(axis_name="x", fence_timeout_s=5.0)
    x = jnp.ones((2,))
    assert sock._fence(x, CommMode.P2P) is x
    assert flags == ["FLAG"]


def test_fence_watchdog_propagates_barrier_errors(monkeypatch):
    def bad(axis):
        raise ValueError("unknown axis")

    monkeypatch.setattr(SOCK.SYNC, "barrier", bad)
    sock = AcceleratorSocket(axis_name="x", fence_timeout_s=5.0)
    with pytest.raises(ValueError, match="unknown axis"):
        sock._fence(jnp.ones((2,)), CommMode.P2P)


def test_fence_watchdog_disabled_by_default(monkeypatch):
    seen = []
    monkeypatch.setattr(SOCK.SYNC, "barrier",
                        lambda axis: seen.append(axis) or "F")
    monkeypatch.setattr(SOCK.SYNC, "ordered_after", lambda x, flag: x)
    sock = AcceleratorSocket(axis_name="x")   # fence_timeout_s=0.0
    sock._fence(jnp.ones((2,)), CommMode.P2P)
    assert seen == ["x"]   # direct call, no thread


# --------------------------------------------------- record / error plumbing ----

def test_faulterror_reexported_from_runtime_fault():
    from repro.core.comm import FaultError as core_err
    from repro.runtime.fault import FaultError as runtime_err
    assert runtime_err is core_err


def test_degraded_reason_compat_alias():
    rec = IssueRecord(site="s", name="n", channel="write", planned="MCAST",
                      issued="MEM", user=0, nbytes=4, impl="x",
                      degraded_reason="why")
    assert rec.degraded == "why"
    SOCK.reset_issue_log()
    SOCK.record_implicit_issue("weights", planned=CommMode.MCAST,
                               issued=CommMode.MEM, impl="xla",
                               reason="gate held", site="t.site")
    entry = SOCK.issued_modes()["t.site"]
    assert entry["degraded_reason"] == "gate held"
    assert entry["degraded"] == "gate held"   # legacy artifact key


# -------------------------------------------- end-to-end under shard_map ----

_LADDER_E2E_CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels.ops import interpret_params
from repro.core.comm import (CommMode, CommPlan, FaultError,
                             TransferDescriptor, register_fusion_target)
from repro.core import socket as SOCK
import repro.kernels.ring_allgather_matmul as RK

mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
ip = interpret_params()
plan = CommPlan({"weights": CommMode.P2P})
register_fusion_target("mlp.up_proj")
gdesc = TransferDescriptor("weights", fused_with="mlp.up_proj",
                           site="t.gather")
x = jax.random.normal(jax.random.key(0), (8 * 4, 16), jnp.float32)
w = jax.random.normal(jax.random.key(1), (16, 8), jnp.float32)

calls = {"n": 0}
def flaky(*a, **k):
    calls["n"] += 1
    raise RuntimeError("NoC link down")
RK.ring_allgather_matmul_local = flaky

sleeps = []
pol = SOCK.RetryPolicy(max_attempts=2, backoff_s=0.001, sleep=sleeps.append)

def run():
    def body(xs, ws):
        s = SOCK.socket_for_axis("x", plan, use_kernels=True, interpret=ip,
                                 retry=pol)
        return s.gather_matmul(xs, ws, gdesc)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)

SOCK.reset_issue_log()
out = run()
# the dead kernel retried once per policy, then the serial rung delivered
# identical numbers under the same P2P verdict, reason attached
assert calls["n"] == 2 and sleeps == [0.001], (calls, sleeps)
np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                           rtol=1e-4, atol=1e-4)
rec = SOCK.issued_records()[-1]
assert rec.impl == "lax_all_gather" and not rec.fused, rec
assert rec.issued == "P2P"
assert rec.degraded_reason and "ladder FUSED_RING->P2P" in rec.degraded_reason
assert SOCK.issued_matches_plan(plan)

# without a policy the same dead kernel crashes the trace (legacy behavior)
calls["n"] = 0
def run_bare():
    def body(xs, ws):
        s = SOCK.socket_for_axis("x", plan, use_kernels=True, interpret=ip)
        return s.gather_matmul(xs, ws, gdesc)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("x", None), P(None, None)),
        out_specs=P(None, None), check_vma=False))(x, w)
try:
    run_bare()
except RuntimeError as e:
    assert "NoC link down" in str(e)
else:
    raise AssertionError("bare socket should not catch kernel errors")
print("LADDER_E2E_OK", flush=True)
"""


def test_ladder_degrades_inside_shard_map(subproc):
    """A dead FUSED_RING kernel inside a real 8-way shard_map trace
    retries per policy, degrades to the serial lax rung with identical
    numerics and a machine-readable reason — and without a policy the
    error still propagates untouched."""
    out = subproc(_LADDER_E2E_CODE, n_devices=8)
    assert "LADDER_E2E_OK" in out
