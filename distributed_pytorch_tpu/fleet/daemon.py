"""The fleet's process boundary: replica daemons, their client proxies,
and the autoscaler that changes how many there are.

Round 14's fleet proved token-exact handoff and rescue with every
``BatcherReplica`` inside one process; this module moves each replica
into its OWN OS process — own device mesh, own telemetry rank lane
(pid-suffixed event files merge into one Chrome trace), own heartbeat
file — speaking the fleet/transport.py RPC (submit / poll / drain /
handoff / heartbeat / readmit / shutdown) over a unix or TCP socket,
with ``KVHandoff.to_bytes`` riding verbatim as the handoff payload.

Three layers:

- **Daemon** (``python -m distributed_pytorch_tpu.fleet.daemon``): the
  server side.  Builds params from ``(seed, cfg)`` — same-seed
  construction IS the cross-process parity mechanism, exactly like
  worker init — wraps a ``BatcherReplica``, serves the ops, and writes
  its bound address to a file ONLY once serving is live, so the
  address file doubles as the readiness barrier (model build + first
  compile happen before it appears).  ``rpc_drop`` chaos hard-exits it
  (``on_drop="exit"``): a real process death, not a simulated one.

- **RemoteReplica / ReplicaProcess**: the client side.  RemoteReplica
  duck-types BatcherReplica's surface (submit / poll / admit / drain /
  orphans / load / page_hashes / kill / close) over an ``RpcClient``,
  so ``FleetRouter`` cannot tell a socket replica from an in-process
  one.  Any transport failure (quarantine, deadline exhaustion, dead
  socket) marks the replica lost and writes a ``transport`` postmortem
  bundle; the router then rescues through the SAME replica-loss path an
  in-process kill takes — gids are bound optimistically before each
  call, so a request lost mid-RPC is an orphan, never a silent drop.

- **FleetAutoscaler**: capacity follows traffic.  Sustained SLO breach
  (RunDoctor's breach/clear hook bus — the loop FleetBreachHook opened,
  closed) or sustained queue growth first re-admits a drained replica,
  else spawns a fresh replica process; sustained idle drains the
  highest-id accepting replica through the existing ``drain``/
  ``readmit`` path (pages travel as handoffs — no recompute, and the
  drained daemon stays warm for the next readmit).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from ..launch import heartbeat_path
from ..utils import monitor, telemetry
from .handoff import KVHandoff
from .replica import ROLES
from .router import FleetRouter
from .transport import (RPC_ATTEMPTS, RPC_DEADLINE_S, RpcClient,
                        RpcRemoteError, RpcServer, TransportError,
                        format_address, parse_address)

# how long make_socket_fleet waits for a daemon's address file — the
# daemon compiles its model before binding, so this bounds cold compile
READY_TIMEOUT_S = 600.0


# ---------------------------------------------------------------------------
# server side: the daemon

def _serve_replica(rep, head: dict, blobs: list[bytes], stop) -> tuple:
    """Dispatch one RPC onto a BatcherReplica.  Runs under the
    RpcServer's per-call critical section — the batcher is never
    entered concurrently."""
    op = head["op"]
    if op == "heartbeat":
        page = getattr(rep.cb, "page", 0) or 0
        return ({"ok": 1, "replica": rep.replica_id, "role": rep.role,
                 "pid": os.getpid(), "page": int(page),
                 "alive": rep.alive, "accepting": rep.accepting,
                 "tick": rep._tick}, [])
    if op == "submit":
        rep.submit(head["gid"],
                   np.asarray(head["prompt"], np.int32),
                   int(head["max_new"]), **head.get("sampling", {}))
        return ({"ok": 1}, [])
    if op == "poll":
        emissions, done, handoffs = rep.poll()
        pages = [k.hex() for k in rep.page_hashes()]
        return ({"emissions": [[g, t] for g, t in emissions],
                 "done": sorted(done),
                 "handoff_gids": [g for g, _ in handoffs],
                 "load": int(rep.load()),
                 "queue": int(rep.queue_depth()),
                 "tick": rep._tick, "alive": rep.alive,
                 "accepting": rep.accepting, "pages": pages},
                [h.to_bytes() for _, h in handoffs])
    if op == "handoff":
        rep.admit(KVHandoff.from_bytes(blobs[0]), head["gid"])
        return ({"ok": 1, "load": int(rep.load())}, [])
    if op == "drain":
        moved = rep.drain()
        return ({"gids": [g for g, _ in moved]},
                [h.to_bytes() for _, h in moved])
    if op == "readmit":
        rep.accepting = True
        return ({"ok": 1}, [])
    if op == "shutdown":
        stop.set()
        return ({"ok": 1}, [])
    raise ValueError(f"unknown op {op!r}")


def main(argv=None) -> int:
    import argparse
    import threading

    ap = argparse.ArgumentParser(
        description="one fleet replica as a daemon process")
    ap.add_argument("--replica-id", type=int, required=True)
    ap.add_argument("--bind", required=True,
                    help="unix:/path.sock | tcp:host:port (port 0 = "
                         "ephemeral; the bound port lands in "
                         "--address-file)")
    ap.add_argument("--address-file", required=True)
    ap.add_argument("--spec-file", required=True,
                    help="JSON: cfg / seed / batcher kwargs / role / "
                         "hb_dir / hb_min_interval_s")
    args = ap.parse_args(argv)

    with open(args.spec_file) as f:
        spec = json.load(f)

    # heavy imports AFTER arg parsing — a bad CLI fails fast
    import jax

    from ..models import transformer as tfm
    from ..serve import ContinuousBatcher
    from ..utils import compile_cache
    from ..utils.logging import get_logger, setup_logging
    from .replica import BatcherReplica

    compile_cache.enable()
    setup_logging()
    log = get_logger("fleet.daemon")
    rid = args.replica_id
    telemetry.maybe_enable(rank=rid, label=f"replica {rid} daemon")

    # jax.config set by CODE in the parent does not cross the process
    # boundary (env-set flags do) — the spec carries any flag that
    # changes numerics, or same-seed init parity silently breaks
    # (jax_threefry_partitionable changes what key(0) generates)
    for flag, value in spec.get("jax_config", {}).items():
        jax.config.update(flag, value)

    cfg = tfm.TransformerConfig(**spec["cfg"])
    # same-seed init on every process = parameter parity with the
    # in-process oracle (the reference's init-parity mechanism)
    params = tfm.init(jax.random.key(int(spec.get("seed", 0))), cfg)
    bkw = dict(spec.get("batcher", {}))
    if "prompt_buckets" in bkw:
        bkw["prompt_buckets"] = tuple(bkw["prompt_buckets"])
    cb = ContinuousBatcher(params, cfg, **bkw)
    rep = BatcherReplica(
        rid, cb, role=spec.get("role", "unified"),
        hb_dir=spec.get("hb_dir"),
        hb_min_interval_s=float(spec.get("hb_min_interval_s", 0.0)))

    stop = threading.Event()
    server = RpcServer(
        parse_address(args.bind),
        lambda head, blobs: _serve_replica(rep, head, blobs, stop),
        replica_id=rid, on_drop="exit")
    # serving is live -> NOW publish the address (atomic, so a polling
    # parent never reads a half-written file)
    tmp = args.address_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(format_address(server.address))
    os.replace(tmp, args.address_file)
    log.info("replica %d serving on %s", rid,
             format_address(server.address))

    # a daemon must not outlive its spawner: an orphaned replica would
    # pin inherited stdio pipes open (hanging any capture of the dead
    # parent's output) and serve a fleet nobody routes to
    ppid = os.getppid()
    while not stop.wait(2.0):
        if os.getppid() != ppid:
            log.warning("replica %d orphaned (parent %d gone); exiting",
                        rid, ppid)
            break
    time.sleep(0.2)  # let the shutdown reply flush before teardown
    server.close()
    rep.close()
    tel = telemetry.active()
    if tel is not None:
        tel.close()
    return 0


# ---------------------------------------------------------------------------
# client side: process handle + replica proxy

class ReplicaProcess:
    """One spawned daemon: owns the subprocess and the readiness wait
    (address-file polling — present means compiled and serving)."""

    def __init__(self, replica_id: int, spec: dict, *,
                 transport: str = "unix", run_dir: str,
                 env: dict | None = None):
        if transport not in ("unix", "tcp"):
            raise ValueError(f"transport {transport!r}: 'unix' | 'tcp'")
        self.replica_id = replica_id
        os.makedirs(run_dir, exist_ok=True)
        spec_path = os.path.join(run_dir, f"replica{replica_id}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        self.address_file = os.path.join(run_dir,
                                         f"replica{replica_id}.addr")
        bind = (f"unix:{os.path.join(run_dir, f'r{replica_id}.sock')}"
                if transport == "unix" else "tcp:127.0.0.1:0")
        penv = dict(os.environ)
        penv.update(telemetry.child_env())  # same run dir, own pid lane
        penv["RANK"] = str(replica_id)      # log lines + fault scoping
        penv.update(env or {})
        # -c import (not -m): the package imports .daemon at init time,
        # so runpy's "found in sys.modules" warning would fire on -m
        self.proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from distributed_pytorch_tpu.fleet.daemon "
             "import main; sys.exit(main())",
             "--replica-id", str(replica_id), "--bind", bind,
             "--address-file", self.address_file,
             "--spec-file", spec_path],
            env=penv)

    @property
    def pid(self) -> int:
        return self.proc.pid

    def wait_address(self, timeout_s: float = READY_TIMEOUT_S) -> tuple:
        t0 = time.monotonic()
        while True:
            try:
                with open(self.address_file) as f:
                    return parse_address(f.read().strip())
            except (OSError, ValueError):
                pass
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {self.replica_id} daemon exited rc="
                    f"{self.proc.returncode} before serving")
            if time.monotonic() - t0 > timeout_s:
                raise TimeoutError(
                    f"replica {self.replica_id} daemon not serving "
                    f"after {timeout_s}s")
            time.sleep(0.05)

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()

    def reap(self, timeout_s: float = 10.0) -> int | None:
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait(timeout=timeout_s)


class RemoteReplica:
    """BatcherReplica's surface over a socket — what FleetRouter holds
    when the replica is another process.

    Liveness is *pessimistic at the transport layer*: the first
    quarantine / deadline exhaustion / dead-socket error on ANY op
    marks the replica lost (``transport`` postmortem bundle written)
    and the router's ordinary replica-loss rescue takes over.  Gids are
    bound BEFORE the RPC that places them, so a request lost mid-call
    is an orphan the rescue re-prefills — never a silent drop.
    Scheduling signals (load, queue depth, page hashes) are mirrors of
    the last poll reply, nudged between polls so LPT placement does not
    pile onto one replica."""

    def __init__(self, replica_id: int, address: tuple, *,
                 role: str = "unified", proc: ReplicaProcess | None = None,
                 hb_dir: str | None = None,
                 deadline_s: float = RPC_DEADLINE_S,
                 attempts: int = RPC_ATTEMPTS):
        if role not in ROLES:
            raise ValueError(f"unknown role {role!r}; one of {ROLES}")
        self.replica_id = replica_id
        self.role = role
        self.proc = proc
        self.alive = True
        self._accepting = True
        self._tick = 0
        self._load = 0
        self._queue = 0
        self._pages: frozenset = frozenset()
        self._bound: set[int] = set()
        self._done: set[int] = set()
        self.client = RpcClient(address, replica_id=replica_id,
                                deadline_s=deadline_s, attempts=attempts)
        self.cb = SimpleNamespace(page=0)   # filled from hello
        self.heartbeat = (
            SimpleNamespace(path=heartbeat_path(hb_dir, replica_id))
            if hb_dir else None)
        self.tel = None
        host = telemetry.active()
        if host is not None:
            self.tel = telemetry.Telemetry(
                host.run_dir, rank=replica_id, gen=host.gen,
                label=f"replica {replica_id} proxy",
                tag=f"_replica{replica_id}proxy")

    @property
    def pid(self) -> int | None:
        return self.proc.pid if self.proc is not None else None

    # -- accepting: the readmit path crosses the socket ------------------
    @property
    def accepting(self) -> bool:
        return self._accepting

    @accepting.setter
    def accepting(self, value: bool) -> None:
        value = bool(value)
        if value and not self._accepting and self.alive:
            if self._call("readmit") is None:
                return  # lost mid-readmit; stays not-accepting
        self._accepting = value

    # -- transport loss --------------------------------------------------
    def _call(self, op: str, head: dict | None = None, blobs=(),
              **kw):
        """One RPC; on transport failure mark this replica lost and
        return None (the caller degrades; the router rescues).  Remote
        handler errors re-raise — the peer is healthy, the call was
        wrong."""
        try:
            return self.client.call(op, head, list(blobs), **kw)
        except RpcRemoteError:
            raise
        except TransportError as e:
            self._lost(str(e))
            return None

    def _lost(self, reason: str) -> None:
        if not self.alive:
            return
        self.alive = False
        self._accepting = False
        if self.tel is not None:
            self.tel.event("peer_quarantined", phase="fleet",
                           replica=self.replica_id, reason=reason)
        monitor.write_postmortem(
            "transport",
            detail={"replica": self.replica_id, "reason": reason,
                    "quarantined": self.client.quarantined,
                    "rpc": dict(self.client.stats)})

    # -- BatcherReplica surface ------------------------------------------
    def submit(self, gid: int, prompt, max_new: int, **kw) -> None:
        if self.role == "decode":
            raise RuntimeError(
                f"replica {self.replica_id} is decode-only: it accepts "
                f"KV handoffs, not fresh prompts")
        self._bound.add(gid)   # optimistic: lost mid-call -> orphan
        rep = self._call("submit", {
            "gid": int(gid),
            "prompt": np.asarray(prompt, np.int32).reshape(-1).tolist(),
            "max_new": int(max_new), "sampling": kw})
        if rep is not None:
            self._load += int(max_new)
            self._queue += 1

    def admit(self, handoff: KVHandoff, gid: int) -> None:
        if self.role == "prefill":
            raise RuntimeError(
                f"replica {self.replica_id} is prefill-only: handoffs "
                f"flow OUT of it")
        self._bound.add(gid)
        rep = self._call("handoff", {"gid": int(gid)},
                         [handoff.to_bytes()])
        if rep is not None:
            self._load = int(rep[0].get("load", self._load))

    def poll(self):
        if not self.alive:
            return [], set(), []
        rep = self._call("poll")
        if rep is None:
            return [], set(), []
        head, blobs = rep
        self._tick = int(head["tick"])
        self._load = int(head["load"])
        self._queue = int(head["queue"])
        self._pages = frozenset(bytes.fromhex(h)
                                for h in head.get("pages", []))
        if not head.get("alive", True):
            # the chaos plan fired INSIDE the daemon's poll (replica_
            # loss there) — surface it as a loss here, same as in-proc
            self._lost("remote replica reported dead")
            return [], set(), []
        emissions = [(int(g), int(t)) for g, t in head["emissions"]]
        done = set(int(g) for g in head["done"])
        self._done |= done
        handoffs = [(int(g), KVHandoff.from_bytes(b))
                    for g, b in zip(head["handoff_gids"], blobs)]
        for g, _ in handoffs:
            self._bound.discard(g)   # moved away; no longer ours
        return emissions, done, handoffs

    def drain(self):
        self._accepting = False
        rep = self._call("drain")
        if rep is None:
            return []
        head, blobs = rep
        out = [(int(g), KVHandoff.from_bytes(b))
               for g, b in zip(head["gids"], blobs)]
        for g, _ in out:
            self._bound.discard(g)
        return out

    def load(self) -> int:
        return self._load

    def queue_depth(self) -> int:
        return self._queue

    def page_hashes(self) -> frozenset:
        return self._pages

    def pending(self) -> bool:
        return self.alive and bool(self._bound - self._done)

    def orphans(self) -> list[int]:
        return [g for g in sorted(self._bound) if g not in self._done]

    def kill(self) -> None:
        """Hard loss from the router's side (stale heartbeat): the
        process is presumed wedged — terminate it and rescue."""
        self.alive = False
        self._accepting = False
        if self.proc is not None:
            self.proc.terminate()

    def close(self) -> None:
        asked = False
        if self.alive and not self.client.quarantined:
            try:
                self.client.call("shutdown", deadline_s=5.0)
                asked = True
            except TransportError:
                pass
        self.client.close()
        if self.proc is not None:
            if not asked:   # no graceful path left — don't wait it out
                self.proc.terminate()
            self.proc.reap()
        if self.tel is not None:
            self.tel.close()


# ---------------------------------------------------------------------------
# fleet construction

def spawn_replica(replica_id: int, spec: dict, *, run_dir: str,
                  transport: str = "unix", role: str = "unified",
                  hb_dir: str | None = None, env: dict | None = None,
                  deadline_s: float = RPC_DEADLINE_S,
                  attempts: int = RPC_ATTEMPTS,
                  ready_timeout_s: float = READY_TIMEOUT_S
                  ) -> RemoteReplica:
    """Spawn one daemon and return its ready proxy (blocks through the
    daemon's model build + compile — the autoscaler's spawn_fn)."""
    proc = ReplicaProcess(
        replica_id, {**spec, "role": role, "hb_dir": hb_dir},
        transport=transport, run_dir=run_dir, env=env)
    address = proc.wait_address(ready_timeout_s)
    rep = RemoteReplica(replica_id, address, role=role, proc=proc,
                        hb_dir=hb_dir, deadline_s=deadline_s,
                        attempts=attempts)
    hello, _ = rep.client.call("heartbeat")
    rep.cb.page = int(hello.get("page", 0))
    return rep


def make_socket_fleet(spec: dict, n: int, *, transport: str = "unix",
                      disaggregate: bool = False,
                      run_dir: str | None = None,
                      hb_stale_s: float | None = None,
                      env: dict | None = None,
                      deadline_s: float = RPC_DEADLINE_S,
                      attempts: int = RPC_ATTEMPTS,
                      ready_timeout_s: float = READY_TIMEOUT_S
                      ) -> FleetRouter:
    """`make_fleet`, but every replica is its own daemon process.

    ``spec`` is the daemon build recipe: ``{"cfg": TransformerConfig
    fields, "seed": int, "batcher": ContinuousBatcher kwargs,
    "jax_config": {flag: value} for numerics-affecting flags the
    parent set by code}`` — same-seed init gives every process (and
    the oracle) identical params.  All daemons spawn first, THEN readiness is awaited, so N
    cold compiles overlap.  Heartbeats always ride a shared hb dir
    under ``run_dir``; pass ``hb_stale_s`` to arm the router's
    stale-heartbeat kill."""
    if n < 1 or (disaggregate and n < 2):
        raise ValueError(f"need >= {2 if disaggregate else 1} replicas")
    run_dir = run_dir or tempfile.mkdtemp(prefix="fleet_rpc_")
    hb_dir = os.path.join(run_dir, "hb")
    os.makedirs(hb_dir, exist_ok=True)
    roles = (["prefill"] + ["decode"] * (n - 1) if disaggregate
             else ["unified"] * n)
    procs = [ReplicaProcess(
        i, {**spec, "role": roles[i], "hb_dir": hb_dir},
        transport=transport, run_dir=run_dir, env=env)
        for i in range(n)]
    reps = []
    for i, proc in enumerate(procs):
        address = proc.wait_address(ready_timeout_s)
        rep = RemoteReplica(i, address, role=roles[i], proc=proc,
                            hb_dir=hb_dir, deadline_s=deadline_s,
                            attempts=attempts)
        hello, _ = rep.client.call("heartbeat")
        rep.cb.page = int(hello.get("page", 0))
        reps.append(rep)
    return FleetRouter(reps, hb_stale_s=hb_stale_s)


# ---------------------------------------------------------------------------
# autoscaling

class FleetAutoscaler:
    """Capacity follows traffic: watch SLO breaches (RunDoctor's
    breach/clear hook bus) and queue backlog, grow on sustained
    pressure, shrink on sustained idle.

    Grow prefers re-admitting a drained-but-alive replica (its daemon
    is warm — reaction is one RPC); only when none exists does
    ``spawn_fn`` (zero-arg -> a ready replica, e.g. a
    ``spawn_replica`` closure) pay a cold start, and the newcomer joins
    via ``router.add_replica``.  Shrink drains the highest-id accepting
    unified/decode replica through the existing drain/readmit path —
    pages travel as handoffs, nothing recomputes, and the drained
    daemon stays warm as the next grow's free capacity.  Call
    ``tick()`` once per router step."""

    def __init__(self, router: FleetRouter, spawn_fn=None, *,
                 min_replicas: int = 1, max_replicas: int = 4,
                 grow_after: int = 3, shrink_after: int = 50,
                 queue_high: int = 4):
        self.router = router
        self.spawn_fn = spawn_fn
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.grow_after = grow_after
        self.shrink_after = shrink_after
        self.queue_high = queue_high
        self._breached: set[str] = set()
        self._pressure = 0
        self._idle = 0
        self.events: list[dict] = []
        self.stats = {"spawned": 0, "readmitted": 0, "drained": 0,
                      "reaction_ticks": 0}

    def register(self, doctor) -> "FleetAutoscaler":
        """Wire into a RunDoctor's breach/clear bus (the FleetBreach-
        Hook pattern): a firing SLO rule is sustained pressure."""
        doctor.on_breach(lambda st: self._breached.add(st.rule.name))
        doctor.on_clear(lambda st: self._breached.discard(st.rule.name))
        return self

    # -- signals ---------------------------------------------------------
    def _live(self):
        return [r for r in self.router.replicas.values() if r.alive]

    def _accepting(self):
        return [r for r in self._live() if r.accepting]

    def _pressured(self) -> bool:
        if self._breached:
            return True
        acc = self._accepting()
        if not acc:
            return True  # zero intake IS pressure
        backlog = sum(r.queue_depth() for r in acc
                      if hasattr(r, "queue_depth"))
        return backlog > self.queue_high * len(acc)

    def _busy(self) -> bool:
        return any(r.load() > 0 or
                   (hasattr(r, "queue_depth") and r.queue_depth() > 0)
                   for r in self._live())

    # -- the loop --------------------------------------------------------
    def tick(self) -> dict | None:
        """One observation; returns the action event if one fired."""
        if self._pressured():
            self._pressure += 1
            self._idle = 0
        elif not self._busy():
            self._idle += 1
            self._pressure = 0
        else:
            self._pressure = self._idle = 0
        if (self._pressure >= self.grow_after
                and len(self._accepting()) < self.max_replicas):
            return self._grow()
        if (self._idle >= self.shrink_after
                and len(self._accepting()) > self.min_replicas):
            return self._shrink()
        return None

    def _event(self, action: str, **kw) -> dict:
        ev = {"action": action, **kw}
        self.events.append(ev)
        self.stats["reaction_ticks"] = self._pressure or self._idle
        self._pressure = self._idle = 0
        tel = telemetry.active()
        if tel is not None:
            tel.event("autoscale", phase="fleet", **ev)
        return ev

    def _grow(self) -> dict | None:
        drained = [r for r in self._live()
                   if not r.accepting and r.role != "decode"]
        if drained:
            rep = min(drained, key=lambda r: r.replica_id)
            self.router.readmit(rep.replica_id)
            self.stats["readmitted"] += 1
            return self._event("readmit", replica=rep.replica_id)
        if self.spawn_fn is None:
            return None
        rep = self.spawn_fn()
        self.router.add_replica(rep)
        self.stats["spawned"] += 1
        return self._event("spawn", replica=rep.replica_id)

    def _shrink(self) -> dict | None:
        cands = [r for r in self._accepting() if r.role != "prefill"]
        if len(cands) <= 1:
            return None  # never drain the last intake/decode capacity
        rep = max(cands, key=lambda r: r.replica_id)
        moved = self.router.drain(rep.replica_id)
        self.stats["drained"] += 1
        return self._event("drain", replica=rep.replica_id,
                           moved=moved)


if __name__ == "__main__":
    sys.exit(main())
