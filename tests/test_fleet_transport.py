"""Multi-process fleet transport tests (fleet/transport.py, daemon.py).

Oracle discipline as tests/test_fleet.py: greedy generation is
dispatch-shape exact, so every stream a SOCKET fleet delivers — across
real daemon processes, injected RPC chaos (`rpc_drop` killing a daemon
mid-stream, `rpc_torn` shipping a truncated reply), quarantine, and
rescue — must match the single-batcher greedy oracle token for token.

The framing matrix truncates the byte stream at every boundary class
(header / payload / crc) and pins that the reader classifies the tear
exactly, the client quarantines the peer (no retry against a lying
write path), and zero tokens are lost or duplicated end to end.  The
autoscaler tests pin grow-on-pressure (SLO breach and queue growth),
shrink-on-idle through drain, and warm readmit preference over cold
spawn.
"""

import io
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_pytorch_tpu import generate as gen
from distributed_pytorch_tpu.fleet import (BatcherReplica,
                                           FleetAutoscaler, FleetRouter,
                                           make_socket_fleet)
from distributed_pytorch_tpu.fleet import transport as tp
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.serve import ContinuousBatcher
from distributed_pytorch_tpu.utils import (compile_cache, faults, monitor,
                                           telemetry)

pytestmark = pytest.mark.fleet

CFG_KW = dict(vocab_size=256, d_model=128, n_layers=2, n_heads=4,
              head_dim=32, n_kv_heads=2, d_ff=256)
CFG = tfm.TransformerConfig(**CFG_KW)
BATCHER_KW = dict(slots=2, max_len=512, temperature=0.0,
                  prompt_buckets=(32,), steps_per_sync=4, paged=True)
SPEC = {"cfg": CFG_KW, "seed": 0,
        "batcher": {**BATCHER_KW, "prompt_buckets": [32]},
        # conftest flips this via jax.config — code-set flags don't
        # cross the exec boundary, so the spec must carry it or the
        # daemons' same-seed init diverges from the oracle's
        "jax_config": {"jax_threefry_partitionable": True}}

# daemons are fresh processes: hand them the suite's persistent compile
# cache (conftest sets it via jax.config, which does NOT cross exec)
DAEMON_ENV = compile_cache.child_env(min_compile_secs=0.5)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.key(0), CFG)


@pytest.fixture(autouse=True)
def _clear_plan():
    yield
    faults.install(None)


def _oracle(params, prompt, max_new):
    return np.asarray(gen.generate(
        params, jnp.asarray(prompt)[None], jax.random.key(1), cfg=CFG,
        max_new=max_new, temperature=0.0))[0]


def _prompts(n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 255, size=int(s)).astype(np.int32)
            for s in rng.integers(5, 17, size=n)]


def _make(params, **kw):
    return ContinuousBatcher(params, CFG, **{**BATCHER_KW, **kw})


# ---------------------------------------------------------------------------
# framing

def test_frame_and_msg_roundtrip():
    head, blobs = {"op": "x", "n": 3}, [b"\x00" * 17, b"pages"]
    payload = tp.encode_msg(head, blobs)
    frame = tp.encode_frame(payload)
    assert tp.read_frame(io.BytesIO(frame)) == payload
    rhead, rblobs = tp.decode_msg(payload)
    assert rhead == head and rblobs == blobs
    # a clean close between frames is a retryable connection error,
    # never a tear
    with pytest.raises(ConnectionError):
        tp.read_frame(io.BytesIO(b""))


@pytest.mark.parametrize("boundary", tp.BOUNDARIES)
def test_truncation_classified_at_every_boundary(boundary):
    """The partial-write matrix: a stream cut inside a frame is a
    TornFrame naming exactly the boundary class the cut landed in."""
    frame = tp.encode_frame(tp.encode_msg({"op": "poll"}, [b"kv" * 40]))
    torn = tp.truncate_frame(frame, boundary)
    assert len(torn) < len(frame)
    with pytest.raises(tp.TornFrame) as ei:
        tp.read_frame(io.BytesIO(torn))
    assert ei.value.boundary == boundary


def test_corrupt_frames_rejected():
    frame = bytearray(tp.encode_frame(b"payload"))
    frame[-1] ^= 0xFF  # crc disagrees
    with pytest.raises(tp.FrameCorrupt, match="crc"):
        tp.read_frame(io.BytesIO(bytes(frame)))
    bad = b"XX" + bytes(frame[2:])
    with pytest.raises(tp.FrameCorrupt, match="magic"):
        tp.read_frame(io.BytesIO(bad))


# ---------------------------------------------------------------------------
# rpc semantics (in-thread servers, no batcher)

def _echo_server(counter=None, **kw):
    def handler(head, blobs):
        if counter is not None:
            counter.append(head["op"])
        return {"ok": head.get("x", 0)}, list(blobs)
    return tp.RpcServer(("tcp", ("127.0.0.1", 0)), handler, **kw)


def test_rpc_roundtrip_and_remote_error():
    srv = _echo_server()
    try:
        cli = tp.RpcClient(srv.address)
        head, blobs = cli.call("ping", {"x": 7}, [b"blob"])
        assert head == {"ok": 7} and blobs == [b"blob"]
        assert cli.stats["calls"] == 1 and cli.stats["retries"] == 0
    finally:
        srv.close()

    def boom(head, blobs):
        raise ValueError("handler bug")
    srv2 = tp.RpcServer(("tcp", ("127.0.0.1", 0)), boom)
    try:
        cli2 = tp.RpcClient(srv2.address)
        # the peer is healthy, the call was wrong: raises, NO quarantine
        with pytest.raises(tp.RpcRemoteError, match="handler bug"):
            cli2.call("x")
        assert not cli2.quarantined
    finally:
        srv2.close()


def test_idempotent_retry_executes_exactly_once():
    """rpc_slow pushes the first attempt past its deadline; the retry
    replays the SAME request key, and the server's dedup cache makes
    sure the handler ran exactly once — the poll-drains-tokens op is
    safe under timeout ambiguity."""
    executed = []
    srv = _echo_server(counter=executed, replica_id=0)
    faults.install(faults.FaultPlan("rpc_slow", step=1, rank=0,
                                    delay_s=0.6, count=1))
    try:
        cli = tp.RpcClient(srv.address, deadline_s=0.2, attempts=3,
                           backoff_base_s=0.01, backoff_cap_s=0.05)
        head, _ = cli.call("poll")
        assert head == {"ok": 0}
        assert cli.stats["retries"] >= 1
        time.sleep(0.7)  # let the slow original finish its dedup lookup
        assert executed == ["poll"]  # once, not once per attempt
    finally:
        srv.close()


@pytest.mark.parametrize("boundary", tp.BOUNDARIES)
def test_torn_reply_quarantines_peer(boundary):
    """A reply truncated at any boundary class means the peer's write
    path is lying: the client quarantines it on the spot — no retry —
    and every later call fails fast."""
    srv = _echo_server(replica_id=0)
    faults.install(faults.FaultPlan("rpc_torn", step=2, rank=0,
                                    mode=boundary, count=1))
    try:
        cli = tp.RpcClient(srv.address, attempts=3)
        cli.call("warm")                      # call 1: clean
        with pytest.raises(tp.PeerQuarantined):
            cli.call("poll")                  # call 2: torn at boundary
        assert cli.quarantined and "TornFrame" in cli.reason
        assert cli.stats["retries"] == 0      # quarantine, not retry
        with pytest.raises(tp.PeerQuarantined):
            cli.call("again")                 # fails without a socket
    finally:
        srv.close()


def test_rpc_fault_op_scoping():
    """An op-scoped plan fires on the first MATCHING call at/past
    ``step`` — never on other ops, however many of them pass — so
    chaos arming survives drift in the call mix (hello probes,
    retries) that shifts raw call indices."""
    faults.install(faults.FaultPlan("rpc_torn", step=3, rank=0,
                                    op="poll", count=1))
    try:
        # calls 1-4: wrong op, some past step — never eligible
        for call in (1, 2, 3, 4):
            assert faults.maybe_rpc_fault(0, call, "heartbeat") is None
        # a matching op below step doesn't fire (and isn't consumed)
        assert faults.maybe_rpc_fault(0, 2, "poll") is None
        plan = faults.maybe_rpc_fault(0, 5, "poll")
        assert plan is not None and plan.kind == "rpc_torn"
        assert faults.maybe_rpc_fault(0, 6, "poll") is None  # count spent
        # an un-scoped plan keeps the index-only semantics
        faults.install(faults.FaultPlan("rpc_drop", step=2, rank=0))
        assert faults.maybe_rpc_fault(0, 1, "poll") is None
        assert faults.maybe_rpc_fault(0, 2, "submit") is not None
    finally:
        faults.reset()


def test_rpc_drop_exhausts_deadline_then_quarantines():
    """rpc_drop kills the endpoint mid-call (on_drop='close' for an
    in-thread server): the op never executes, retries find a dead
    endpoint, and the budget exhausts into RpcDeadline quarantine."""
    executed = []
    srv = _echo_server(counter=executed, replica_id=0, on_drop="close")
    faults.install(faults.FaultPlan("rpc_drop", step=2, rank=0, count=1))
    try:
        cli = tp.RpcClient(srv.address, deadline_s=0.3, attempts=2,
                           backoff_base_s=0.01, backoff_cap_s=0.05)
        cli.call("warm")
        with pytest.raises(tp.PeerQuarantined):
            cli.call("poll")
        assert "RpcDeadline" in cli.reason
        assert executed == ["warm"]  # the dropped op never ran
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# socket fleets (real daemon processes)

def _check_token_exact(params, res, prompts, max_new):
    assert len(res) == len(prompts)
    for i, (gid, out) in enumerate(sorted(res.items())):
        oracle = _oracle(params, prompts[i], max_new)
        assert np.array_equal(out, oracle), (
            f"gid {gid}: fleet {out.tolist()} != oracle "
            f"{oracle.tolist()}")


def test_socket_fleet_token_exact_tcp(params, tmp_path):
    """A clean 2-daemon TCP fleet delivers every stream token-exact vs
    the in-process greedy oracle — same-seed init IS param parity."""
    prompts = _prompts(3)
    fleet = make_socket_fleet(SPEC, 2, transport="tcp",
                              run_dir=str(tmp_path), env=DAEMON_ENV)
    try:
        res = fleet.run(prompts, max_new=10)
    finally:
        fleet.close()
    _check_token_exact(params, res, prompts, 10)
    assert fleet.stats["replicas_lost"] == 0
    # rpc accounting flowed: every replica's client measured round-trips
    for rep in fleet.replicas.values():
        assert rep.client.stats["calls"] > 0
        assert rep.client.stats["rpc_ms"] > 0.0
        assert rep.proc.proc.poll() == 0  # graceful shutdown, rc 0


def test_socket_fleet_rpc_drop_rescue_token_exact(params, tmp_path):
    """The acceptance chaos: an rpc_drop plan hard-exits replica 1's
    daemon mid-stream (a REAL process death).  The client's retries
    find a dead socket, the peer is quarantined, a transport postmortem
    lands, and the router rescues every orphan onto replica 0 — zero
    lost, zero duplicated tokens."""
    tel = telemetry.enable(str(tmp_path / "tel"), rank=0)
    # op-scoped: fire on the first POLL at/past call 5 — mid-stream
    # whatever hello probes / retries shift the raw call indices to
    plan = faults.FaultPlan("rpc_drop", step=5, rank=1, op="poll")
    prompts = _prompts(4)
    fleet = make_socket_fleet(
        SPEC, 2, transport="unix", run_dir=str(tmp_path),
        env={**DAEMON_ENV, faults.ENV_VAR: plan.to_env()},
        deadline_s=2.0)
    try:
        res = fleet.run(prompts, max_new=10)
    finally:
        fleet.close()
        telemetry.disable()
    _check_token_exact(params, res, prompts, 10)
    assert fleet.stats["replicas_lost"] == 1, (
        dict(fleet.stats),
        {i: dict(r.client.stats) for i, r in fleet.replicas.items()})
    assert fleet.stats["rescued"] >= 1
    # the daemon really died, with the fault exit code
    assert fleet.replicas[1].proc.proc.returncode == faults.FAULT_EXIT_CODE
    assert fleet.replicas[1].client.quarantined
    # flight recorder: a transport-class bundle was written
    bundles = [json.loads((tmp_path / "tel" / p).read_text())
               for p in os.listdir(tmp_path / "tel")
               if p.startswith(monitor.BUNDLE_PREFIX)]
    tb = [b for b in bundles if b["trigger"]["kind"] == "transport"]
    assert tb and tb[0]["trigger"]["replica"] == 1
    assert "RpcDeadline" in tb[0]["trigger"]["reason"]


def test_socket_fleet_rpc_torn_rescue_token_exact(params, tmp_path):
    """rpc_torn ships replica 1's reply truncated mid-frame: the peer
    is quarantined IMMEDIATELY (no retry against a corrupting writer),
    and the rescue path still reassembles every stream token-exact —
    the tokens the executed-but-unreported op drained are re-derived by
    the greedy re-prefill, never duplicated."""
    plan = faults.FaultPlan("rpc_torn", step=5, rank=1, mode="payload",
                            op="poll")
    prompts = _prompts(4, seed=11)
    fleet = make_socket_fleet(
        SPEC, 2, transport="unix", run_dir=str(tmp_path),
        env={**DAEMON_ENV, faults.ENV_VAR: plan.to_env()},
        deadline_s=2.0)
    try:
        res = fleet.run(prompts, max_new=10)
    finally:
        fleet.close()
    _check_token_exact(params, res, prompts, 10)
    assert fleet.stats["replicas_lost"] == 1, (
        dict(fleet.stats),
        {i: dict(r.client.stats) for i, r in fleet.replicas.items()})
    cli = fleet.replicas[1].client
    assert cli.quarantined and "TornFrame" in cli.reason


# ---------------------------------------------------------------------------
# autoscaler

def test_autoscaler_grow_shrink_readmit(params):
    """Queue growth spawns; idle drains (pages travel, nothing is
    recomputed); renewed pressure re-admits the warm drained replica
    instead of paying a cold spawn."""
    make = lambda: _make(params)
    router = FleetRouter([BatcherReplica(0, make)])
    spawned = []

    def spawn():
        rep = BatcherReplica(1 + len(spawned), make)
        spawned.append(rep.replica_id)
        return rep

    sc = FleetAutoscaler(router, spawn, min_replicas=1, max_replicas=2,
                         grow_after=2, shrink_after=3, queue_high=1)
    prompts = _prompts(8, seed=5)
    gids = [router.submit(p, 8) for p in prompts]
    for _ in range(300):
        router.step()
        sc.tick()
        if not router.pending() and sc.stats["drained"]:
            break
    assert sc.stats["spawned"] == 1 and spawned == [1]
    assert sc.stats["drained"] == 1
    assert [e["action"] for e in sc.events] == ["spawn", "drain"]
    assert len(router._intake()) == 1  # back to one accepting replica
    for gid, p in zip(gids, prompts):
        assert np.array_equal(router.result(gid), _oracle(params, p, 8))
    # renewed pressure: the drained replica is warm — readmit, no spawn
    for p in _prompts(8, seed=6):
        router.submit(p, 8)
    for _ in range(300):
        router.step()
        if sc.tick() is not None:
            break
    assert sc.stats["readmitted"] == 1 and sc.stats["spawned"] == 1
    assert sc.events[-1]["action"] == "readmit"
    while router.pending():
        router.step()


def test_autoscaler_slo_breach_spawns(params, tmp_path):
    """The RunDoctor loop closes: a sustained SLO breach (real rule,
    real breach transition over the event stream) is pressure — the
    autoscaler spawns without any queue backlog at all."""
    tel = telemetry.enable(str(tmp_path), rank=0)
    doctor = monitor.RunDoctor([monitor.SloRule(
        name="ttft", metric="ttft_ms", threshold=100.0, op="<=",
        window=4, agg="mean", record="gauge", min_samples=2)])
    router = FleetRouter([BatcherReplica(0, lambda: _make(params))])
    sc = FleetAutoscaler(router,
                         lambda: BatcherReplica(1, lambda: _make(params)),
                         max_replicas=2, grow_after=2).register(doctor)
    try:
        assert doctor.attach(tel)
        for _ in range(4):
            tel.gauge("ttft_ms", 900.0, phase="serve")
        assert sc._breached  # the breach crossed the hook bus
        assert sc.tick() is None      # sustained means grow_after ticks
        ev = sc.tick()
        assert ev is not None and ev["action"] == "spawn"
        assert 1 in router.replicas
        # clear lifts the pressure
        for _ in range(8):
            tel.gauge("ttft_ms", 1.0, phase="serve")
        assert not sc._breached
        assert sc.tick() is None
    finally:
        doctor.detach()
        telemetry.disable()


def test_remote_replica_surface_matches_batcher_replica():
    """RemoteReplica must keep duck-typing BatcherReplica — the router
    cannot tell them apart, so the surfaces may not drift."""
    from distributed_pytorch_tpu.fleet import RemoteReplica
    for name in ("submit", "admit", "poll", "drain", "load",
                 "page_hashes", "queue_depth", "pending", "orphans",
                 "kill", "close"):
        assert callable(getattr(BatcherReplica, name)), name
        assert callable(getattr(RemoteReplica, name)), name
