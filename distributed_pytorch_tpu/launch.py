"""Multi-process launcher: the torchrun equivalent, with failure detection.

The reference launches DDP via ``torchrun --nproc_per_node=1 --nnodes=4
--node_rank=R --master_addr=M --master_port=6585 main_ddp.py`` (reference
start_ddp.sh:1) — torchrun's elastic agent spawns the worker and exports the
MASTER_ADDR/MASTER_PORT/WORLD_SIZE/LOCAL_WORLD_SIZE/LOCAL_RANK/RANK env-var
convention that main_ddp.py:93-100 reads.  This module is the framework's own
launcher speaking the same contract:

  python -m distributed_pytorch_tpu.launch --nnodes 4 --node-rank R \
      --master-addr M --master-port 6585 -- \
      -m distributed_pytorch_tpu.cli --rendezvous env --strategy ddp

Two deliberate upgrades over the reference's setup:

- **Failure detection.** The reference's ``timeout=None`` rendezvous
  (main_all_reduce.py:96) and unconfigured torchrun (no ``--max_restarts``,
  start_ddp.sh:1) mean a dead peer hangs the gang forever (SURVEY.md 2.3/5).
  Here the agent polls its children; when one exits non-zero, the rest are
  terminated (SIGTERM, then SIGKILL after a grace period) and the gang is
  either restarted (``--max-restarts N``, elastic-style) or the launcher
  exits with the failed worker's code.  SIGTERM to the launcher itself also
  tears the gang down (no orphaned workers holding chips).

  Multi-node restarts are COORDINATED through a generation-numbered
  rendezvous (torchrun's round concept): the node-0 agent hosts a tiny TCP
  coordinator (master_port+1); every agent passes a barrier per generation
  before spawning, reports local worker failures to the coordinator, and
  polls it so a death on ANY node tears down every node's workers within
  the monitor interval.  All agents then rejoin the barrier for generation
  g+1 and respawn together — no mixed-generation gangs.  Workers see their
  generation as ``RESTART_ATTEMPT`` (checkpoint/resume hook).
- **TPU process model.** On TPU one *process per host* owns all local chips
  (JAX single-controller-per-host), so ``--nproc-per-node`` defaults to 1 and
  values >1 are for CPU simulation/testing, where each worker is given a
  disjoint slice of fake devices.  Every worker inherits the agent's device
  environment, so with >1 workers on a TPU host each would claim every
  chip and all but the first would fail or hang: the agent refuses that
  shape unless the environment pins workers to the CPU
  (``JAX_PLATFORMS=cpu``).
- **Elastic resize** (``--elastic --min-nodes M --max-nodes N``, round 12).
  Restart-at-the-same-size costs the whole gang for one lost member; elastic
  mode makes a worker loss cost a RESHARD instead.  The agent gains
  heartbeat-based liveness (workers publish ``hb_rank<R>.json`` into
  ``ELASTIC_DIR`` each step — a HUNG straggler is detected by heartbeat
  staleness, not just a dead PID), and on worker loss with at least
  ``min_nodes`` survivors it drives a GENERATION BUMP instead of a restart:
  survivors are drained gracefully (SIGTERM -> they exit the step loop at a
  sync point, flush a checkpoint, and exit ``ELASTIC_DRAIN_EXIT_CODE``),
  then the gang re-rendezvouses at the smaller world size and resumes from
  the last-good checkpoint, resharded across the new topology
  (parallel/elastic.py is the worker-side half; utils/checkpoint.py
  ``load_resharded`` is the reshard).  When the lost slot becomes eligible
  again (``rejoin_delay_s``) and the shrunk gang has provably advanced
  (heartbeat steps moved >= ``grow_after_steps``), the same machinery GROWS
  the gang back at the next boundary.  Both transitions are recorded as
  ``GangResult.resize_events``; drain outcomes (how many workers flushed vs
  needed SIGKILL) land in ``GangResult.drain``.  Elastic mode currently
  drives ONE agent's workers (``--nnodes 1``, the CPU-simulation topology
  every gang test uses; one worker == one "node"); coordinated multi-agent
  membership is the carried-forward half (ROADMAP).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

# jax-free by design (the agent must never compete with workers for
# chips): utils/__init__ resolves submodules lazily (PEP 562), and both
# utils.telemetry and utils.logging import no jax — the agent's gang
# lifecycle events and structured logs ride the same machinery as the
# workers' without breaking the process-model contract above.
from .utils import monitor, telemetry
from .utils.logging import get_logger, setup_logging


def _tel_event(name: str, **args) -> None:
    """Gang lifecycle on the unified timeline (round 13): worker
    start/exit, heartbeat staleness, drain outcomes, and resize
    generations land as events in the 'gang' lane when the agent runs
    with --telemetry-dir; free otherwise.  The agent registers as
    pid -1 ("agent") in the merged trace; its CURRENT generation rides
    in args (the registry's gen is per-process, and the agent spans
    every generation)."""
    tel = telemetry.active()
    if tel is not None:
        tel.event(name, phase="gang", **args)

# Exit code of chaos-harness-injected crashes.  Kept in sync with
# utils/faults.FAULT_EXIT_CODE rather than imported: faults.py imports
# jax, and the agent process must stay jax-free (it supervises workers;
# it must never compete with them for chips or import time).  Pinned by
# tests/test_faults.py::test_fault_exit_code_constants_agree.
FAULT_EXIT_CODE = 77

# Elastic-gang exit codes (round 12).  Workers use them to tell the agent
# HOW they left; the agent must never confuse either with a failure.
# Defined here (the jax-free side) and imported by parallel/elastic.py —
# the worker-side half — so the two can never drift.
#
# DRAIN: the worker honored an agent-initiated drain (SIGTERM) at a step
# boundary — it flushed its checkpoint and exited ready to re-rendezvous.
ELASTIC_DRAIN_EXIT_CODE = 78
# RESIZE: the worker itself REQUESTS a gang resize (the training sentry's
# escalation rung between rollback-and-skip and abort): it rolled back to
# last-good, checkpointed, and left at a sync point.  The agent treats the
# exit like a lost worker — survivors drain and the gang re-rendezvouses
# one smaller — but classifies the event as "requested".
ELASTIC_RESIZE_EXIT_CODE = 79

# Env contract the elastic agent exports to workers (beyond the torchrun
# vars): the heartbeat/run directory and the resize bounds.
ELASTIC_DIR_ENV = "ELASTIC_DIR"
ELASTIC_MIN_ENV = "ELASTIC_MIN_NODES"
ELASTIC_MAX_ENV = "ELASTIC_MAX_NODES"
HEARTBEAT_PREFIX = "hb_rank"  # hb_rank<R>.json, written atomically

DEFAULT_PORT = 6585  # reference start_ddp.sh:1 / main_all_reduce.py:96
TERM_GRACE_S = 10.0
BARRIER_TIMEOUT_S = 600.0   # max skew between agents reaching a generation
RPC_TIMEOUT_S = 5.0         # status/fail round-trip budget
CONNECT_RETRY_S = 60.0      # waiting for the node-0 coordinator to come up


# ---------------------------------------------------------------------------
# heartbeat reading + liveness verdicts: ONE copy, shared by the elastic
# agent below and the serving fleet's router (fleet/router.py).  Both
# supervise members that publish atomic hb_rank<R>.json beacons
# (parallel/elastic.Heartbeat, fleet/replica.BatcherReplica), and both
# need the same judgment call: a member that has NEVER beaten is a cold
# start (long compile) judged by PID liveness alone, never by silence.

def heartbeat_path(run_dir: str, rank: int) -> str:
    """Where member ``rank``'s beacon lands (the Heartbeat contract)."""
    return os.path.join(run_dir, f"{HEARTBEAT_PREFIX}{rank}.json")


def read_heartbeat(path: str) -> dict | None:
    """One atomically-published beacon: {rank, step, gen, time, age_s};
    None for missing/torn/half-typed files (beats are tmp+rename, so
    the next one lands whole — a missed read is late detection, not a
    death).  ``time`` is informational and optional — age is judged
    from the file's mtime, so beacons that publish only
    {rank, step, gen} stay supervisable."""
    try:
        with open(path) as f:
            hb = json.load(f)
        mtime = os.path.getmtime(path)
        return {"rank": int(hb["rank"]), "step": int(hb["step"]),
                "gen": int(hb["gen"]), "time": float(hb.get("time", mtime)),
                "age_s": time.time() - mtime}
    except (OSError, ValueError, KeyError, TypeError):
        return None


def pid_alive(pid: int | None) -> bool:
    """POSIX existence probe (signal 0).  Permission errors mean the
    process exists; no pid to probe reads as dead."""
    if not pid:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True
    return True


def heartbeat_verdict(hb: dict | None, *, stale_s: float,
                      gen: int | None = None,
                      pid: int | None = None) -> str:
    """Classify one member from its newest beat (``read_heartbeat``):

    - ``"cold"`` — never beaten (in generation ``gen``, when given):
      still compiling / still spawning.  Silence before the first beat
      must NEVER read as a hang;
    - ``"lost"`` — cold AND the given ``pid`` is gone: the process died
      before it ever beat (the only judgment PID liveness may make);
    - ``"fresh"`` — newest beat younger than ``stale_s``;
    - ``"stale"`` — beaten, then silent past ``stale_s``: a HUNG member
      (wedged collective, live PID), the case PID polling cannot see.
    """
    if hb is None or (gen is not None and hb["gen"] != gen):
        return ("lost" if pid is not None and not pid_alive(pid)
                else "cold")
    return "stale" if hb["age_s"] > stale_s else "fresh"


class _Coordinator:
    """Generation rendezvous service hosted by the node-0 agent.

    The barrier counts CHANGING membership (round 19, the carried
    elastic half): it releases a generation when every CURRENT member
    has arrived — not a fixed ``nnodes`` — so ``join``/``leave`` let
    the gang grow/shrink between generations without a fixed-size
    rendezvous.  A ``leave`` during a wait re-evaluates the barrier
    (the departed node must not wedge survivors), and barrier replies
    carry the membership the generation rendezvoused at, so arrivals
    spawn at the CURRENT world size.  With membership never touched,
    every condition degrades to the fixed-``nnodes`` behavior.

    One JSON message per TCP connection:
      {"op": "barrier", "node": R, "gen": G} -> blocks until every
          current member arrives at generation G (or abort) ->
          {"ok": bool, "abort", "world_size", "members"}
      {"op": "join", "node": R}              -> R becomes a member from
          the next barrier on -> {"ok", "world_size", "members"}
      {"op": "leave", "node": R}             -> R stops being counted
          (and stops blocking any in-flight barrier) -> same reply
      {"op": "fail", "gen": G, "code": C}    -> records G as failed
      {"op": "status", "gen": G}             -> {"failed", "code", "abort"}
      {"op": "done", "node": R}              -> node R is finished (its own
          gang result is settled): no further generations, but running
          gangs are NOT torn down
      {"op": "abort"}                        -> no further generations AND
          running workers should be terminated (fatal)
    """

    def __init__(self, nnodes: int, port: int):
        self.nnodes = nnodes
        self.members: set[int] = set(range(nnodes))
        self.cond = threading.Condition()
        self.arrived: dict[int, set[int]] = {}
        self.failed: dict[int, int] = {}
        self.abort = False
        self.done = False
        self.finished: set[int] = set()
        self.srv = socket.create_server(("0.0.0.0", port))
        threading.Thread(target=self._serve, daemon=True).start()

    def _membership(self) -> dict:
        # callers hold self.cond
        return {"world_size": len(self.members),
                "members": sorted(self.members)}

    def _serve(self) -> None:
        while True:
            try:
                conn, _ = self.srv.accept()
            except OSError:  # closed
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True).start()

    def _handle(self, conn: socket.socket) -> None:
        with conn:
            try:
                # Bound the request read: a client that connects but never
                # sends a line must not pin this handler thread (and, for
                # 'barrier', the condition path) forever.  Barrier gets the
                # long budget — its request line may lag a slow agent.
                conn.settimeout(BARRIER_TIMEOUT_S)
                msg = json.loads(conn.makefile("r").readline())
                op = msg["op"]
                if op == "barrier":
                    gen = msg["gen"]
                    with self.cond:
                        self.arrived.setdefault(gen, set()).add(msg["node"])
                        self.cond.notify_all()
                        # every CURRENT member present (membership may
                        # shrink mid-wait — re-evaluated on notify)
                        ok = self.cond.wait_for(
                            lambda: (self.members
                                     <= self.arrived.get(gen, set())
                                     or self.abort or self.done),
                            timeout=BARRIER_TIMEOUT_S)
                        reply = {"ok": (bool(ok) and not self.abort
                                        and not self.done),
                                 "abort": self.abort,
                                 **self._membership()}
                elif op in ("join", "leave"):
                    node = int(msg["node"])
                    with self.cond:
                        if op == "join":
                            self.members.add(node)
                        else:
                            self.members.discard(node)
                        self.cond.notify_all()
                        reply = {"ok": True, **self._membership()}
                elif op == "fail":
                    with self.cond:
                        self.failed.setdefault(msg["gen"],
                                               int(msg.get("code", 1)))
                        self.cond.notify_all()
                    reply = {"ok": True}
                elif op == "done":
                    with self.cond:
                        self.done = True
                        self.finished.add(int(msg.get("node", -1)))
                        self.cond.notify_all()
                    reply = {"ok": True}
                elif op == "abort":
                    with self.cond:
                        self.abort = True
                        self.cond.notify_all()
                    reply = {"ok": True}
                else:  # status
                    gen = msg["gen"]
                    with self.cond:
                        reply = {"failed": gen in self.failed,
                                 "code": self.failed.get(gen, 0),
                                 "abort": self.abort}
                conn.sendall((json.dumps(reply) + "\n").encode())
            except (OSError, ValueError, KeyError):
                pass

    def wait_all_finished(self, timeout: float) -> bool:
        """Block until every CURRENT member has reported done (so peers
        still polling never see a vanished coordinator; departed members
        owe nothing); False on timeout."""
        with self.cond:
            return self.cond.wait_for(
                lambda: self.members <= self.finished, timeout=timeout)

    def close(self) -> None:
        try:
            self.srv.close()
        except OSError:
            pass


def _rpc(addr: str, port: int, msg: dict, timeout: float) -> dict:
    with socket.create_connection((addr, port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall((json.dumps(msg) + "\n").encode())
        return json.loads(s.makefile("r").readline())


@dataclass
class WorkerSpec:
    """One worker process's identity within the gang (the env contract of
    reference main_ddp.py:93-100)."""

    rank: int
    local_rank: int
    node_rank: int
    world_size: int
    local_world_size: int
    master_addr: str
    master_port: int

    def env(self) -> dict[str, str]:
        env = dict(os.environ)
        env.update(
            MASTER_ADDR=self.master_addr,
            MASTER_PORT=str(self.master_port),
            WORLD_SIZE=str(self.world_size),
            LOCAL_WORLD_SIZE=str(self.local_world_size),
            RANK=str(self.rank),
            LOCAL_RANK=str(self.local_rank),
            NODE_RANK=str(self.node_rank),
        )
        return env


@dataclass
class ElasticConfig:
    """Elastic-gang policy for one agent (round 12).

    ``min_workers``/``max_workers`` bound the gang size (one worker == one
    "node" in the single-agent topology).  ``heartbeat_timeout_s`` is the
    hung-straggler bound: a worker whose newest CURRENT-GENERATION
    heartbeat is older than this is killed and treated as lost (a worker
    that never beat — e.g. still compiling — is judged by PID only, so a
    long cold compile cannot be misread as a hang).  ``drain_grace_s`` is
    how long survivors get to reach a sync point, flush their checkpoint
    and exit ``ELASTIC_DRAIN_EXIT_CODE`` before SIGKILL.  A lost slot
    becomes respawn-eligible ``rejoin_delay_s`` after the loss, and the
    gang grows back only once every live worker's heartbeat step has
    advanced >= ``grow_after_steps`` within the current generation — the
    shrunk gang must provably train (and hence checkpoint) before the
    grow-back costs another reshard."""

    min_workers: int = 1
    max_workers: int = 1
    heartbeat_timeout_s: float = 300.0
    drain_grace_s: float = 30.0
    rejoin_delay_s: float = 0.0
    grow_after_steps: int = 1
    # Resize budget: total SHRINKS the run may absorb before the gang is
    # declared failed (grow-backs are free).  Without a cap, a slot that
    # deterministically crashes (bad host, poisoned env) would drive an
    # unbounded shrink/grow oscillation; with one, the repeated loss
    # eventually surfaces as the failure it is.  ``--max-restarts`` is
    # NOT consulted in elastic mode — resizes replace restarts.
    max_resizes: int = 16
    run_dir: str | None = None  # heartbeat dir (default: mkdtemp)

    def __post_init__(self):
        if not 1 <= self.min_workers <= self.max_workers:
            raise ValueError(
                f"elastic bounds must satisfy 1 <= min <= max, got "
                f"[{self.min_workers}, {self.max_workers}]")
        if self.max_resizes < 1:
            raise ValueError(
                f"max_resizes must be >= 1, got {self.max_resizes}")


@dataclass
class GangResult:
    """Outcome of one gang attempt.

    ``injected_failures`` counts worker deaths the agent CLASSIFIED as
    fault-injected (exit code ``faults.FAULT_EXIT_CODE`` — the chaos
    harness's distinctive code, utils/faults.py) across all generations;
    they feed the same ``--max-restarts`` budget as genuine failures
    (an injected crash must exercise the REAL restart path), but the
    classification separates "the chaos test fired" from "production
    fell over" in logs and results.

    ``resize_events`` (elastic mode) records every world-size change as
    ``{"gen", "kind" ("shrink"/"grow"), "from_size", "to_size",
    "reason", "rank"}``; ``drain`` accumulates graceful-drain outcomes
    across all teardowns: how many workers exited the step loop cleanly
    on SIGTERM ("drained" = flushed-checkpoint DRAIN exits, "exited" =
    other voluntary exits) versus had to be SIGKILLed ("killed")."""

    returncode: int
    failed_rank: int | None = None
    restarts_used: int = 0
    per_rank: dict[int, int] = field(default_factory=dict)
    injected_failures: int = 0
    resize_events: list = field(default_factory=list)
    drain: dict = field(default_factory=dict)

    @property
    def injected(self) -> bool:
        """The FINAL failure (if any) was a classified injected fault."""
        return self.returncode == FAULT_EXIT_CODE


class LocalAgent:
    """Spawns and supervises this node's workers (torchrun's elastic agent).

    ``argv`` is passed to the Python interpreter verbatim, so both script
    paths (``train.py ...``) and modules (``-m pkg.cli ...``) work.
    """

    def __init__(
        self,
        argv: list[str],
        *,
        nnodes: int = 1,
        node_rank: int = 0,
        nproc_per_node: int = 1,
        master_addr: str = "127.0.0.1",
        master_port: int = DEFAULT_PORT,
        max_restarts: int = 0,
        monitor_interval_s: float = 0.1,
        agent_port: int | None = None,
        elastic: ElasticConfig | None = None,
        log=print,
    ):
        self.argv = argv
        self.nnodes = nnodes
        self.node_rank = node_rank
        self.nproc = nproc_per_node
        self.master_addr = master_addr
        self.master_port = master_port
        self.max_restarts = max_restarts
        self.monitor_interval_s = monitor_interval_s
        # coordinator endpoint (nnodes > 1): node 0 hosts, everyone dials
        self.agent_port = (agent_port if agent_port is not None
                           else master_port + 1)
        self.elastic = elastic
        if (nproc_per_node > 1 and os.environ.get(
                "JAX_PLATFORMS", "").strip().lower() != "cpu"):
            raise ValueError(
                f"--nproc-per-node {nproc_per_node} needs workers pinned "
                f"to the CPU (JAX_PLATFORMS=cpu in the agent's "
                f"environment): workers inherit the agent's device "
                f"environment, and on an accelerator host each would "
                f"claim every local chip.  The supported shape there is "
                f"one worker per host driving all local chips "
                f"(scripts/start_ddp.sh)")
        if elastic is not None and nnodes > 1:
            raise ValueError(
                "elastic resize drives one agent's workers (nnodes=1, the "
                "worker-per-'node' CPU-simulation topology); coordinated "
                "multi-agent membership is the carried-forward half "
                "(ROADMAP 'Elastic gang + async relaxations')")
        # agent log lines also feed the monitor's bounded log ring so a
        # postmortem bundle carries the supervision trail

        def _log(msg, _inner=log):
            monitor.log_line(str(msg))
            _inner(msg)
        self.log = _log
        self._procs: dict[int, subprocess.Popen] = {}
        self._gen = 0  # current rendezvous generation (RESTART_ATTEMPT)
        # the membership the newest barrier rendezvoused at (None until
        # a coordinated generation has passed one) — _barrier records it
        self._barrier_world: int | None = None
        # graceful-drain accounting across every teardown of this run
        # (satellite: _terminate_all outcome rides GangResult.drain)
        self._drain_stats = {"drained": 0, "exited": 0, "killed": 0}

    def specs(self) -> list[WorkerSpec]:
        return self._specs_for(self.nproc)

    def _specs_for(self, nproc: int) -> list[WorkerSpec]:
        world = self.nnodes * nproc
        return [
            WorkerSpec(
                rank=self.node_rank * nproc + lr,
                local_rank=lr,
                node_rank=self.node_rank,
                world_size=world,
                local_world_size=nproc,
                master_addr=self.master_addr,
                master_port=self.master_port,
            )
            for lr in range(nproc)
        ]

    # -- process management ------------------------------------------------
    def _spawn(self, nproc: int | None = None,
               extra_env: dict[str, str] | None = None) -> None:
        for spec in self._specs_for(nproc if nproc is not None
                                    else self.nproc):
            cmd = [sys.executable] + self.argv
            env = spec.env()
            env["RESTART_ATTEMPT"] = str(self._gen)
            if extra_env:
                env.update(extra_env)
            self._procs[spec.rank] = subprocess.Popen(cmd, env=env)
            self.log(f"[launch] node {self.node_rank}: started rank "
                     f"{spec.rank} (pid {self._procs[spec.rank].pid})")
            _tel_event("worker_start", rank=spec.rank, gen=self._gen,
                       pid=self._procs[spec.rank].pid,
                       world_size=spec.world_size)

    def _terminate_all(self, grace_s: float = TERM_GRACE_S) -> dict:
        """Graceful drain: SIGTERM the gang first (workers may reach a
        sync point, flush their last checkpoint, and exit — the elastic
        contract exits ``ELASTIC_DRAIN_EXIT_CODE``), escalate to SIGKILL
        only after ``grace_s``.  Returns this teardown's outcome counts
        and accumulates them into the run-wide ``GangResult.drain``
        accounting: {"drained": DRAIN-code exits, "exited": other
        voluntary exits under SIGTERM, "killed": needed SIGKILL}."""
        outcome = {"drained": 0, "exited": 0, "killed": 0}
        live = [p for p in self._procs.values() if p.poll() is None]
        for p in live:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        deadline = time.monotonic() + grace_s
        for p in live:
            while p.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()
                outcome["killed"] += 1
            elif p.returncode == ELASTIC_DRAIN_EXIT_CODE:
                outcome["drained"] += 1
            else:
                outcome["exited"] += 1
        for k, v in outcome.items():
            self._drain_stats[k] += v
        if live:
            _tel_event("gang_drain", gen=self._gen, **outcome)
        return outcome

    def _gang_view(self, size: int | None = None) -> dict:
        """Gang membership as the agent sees it (the bundle's ``gang``
        section): topology, generation, and each rank's exit state."""
        return {
            "nnodes": self.nnodes, "node_rank": self.node_rank,
            "world_size": self.nnodes * (size if size is not None
                                         else self.nproc),
            "gen": self._gen,
            "ranks": {r: p.poll() for r, p in self._procs.items()},
        }

    def _postmortem(self, trigger: str, size: int | None = None,
                    **detail) -> str | None:
        """Flight recorder at the agent's failure-classification points
        (round 15).  Only fires when the run has a telemetry dir — the
        agent's own registry or the exported TELEMETRY_DIR the workers
        wrote to; a bare gang has nowhere to put a bundle."""
        tel = telemetry.active()
        run_dir = (tel.run_dir if tel is not None
                   else os.environ.get(telemetry.TELEMETRY_DIR_ENV))
        if not run_dir:
            return None
        return monitor.write_postmortem(
            trigger, run_dir=run_dir, tel=tel, detail=detail,
            gang=self._gang_view(size))

    def _monitor(self, watch_remote: bool = False) -> GangResult:
        """Block until the gang finishes or any worker fails.

        This is the failure *detection* the reference lacks: a non-zero or
        signal-killed worker is noticed within ``monitor_interval_s`` and
        the survivors are torn down instead of hanging in a collective.
        With ``watch_remote`` the coordinator is polled too, so a worker
        death on ANOTHER node tears this node's workers down as promptly.
        """
        last_remote_check = 0.0
        while True:
            running = False
            for rank, p in self._procs.items():
                code = p.poll()
                if code is None:
                    running = True
                elif code != 0:
                    kind = ("injected fault" if code == FAULT_EXIT_CODE
                            else "failure")
                    self.log(f"[launch] rank {rank} FAILED with exit code "
                             f"{code} ({kind}); terminating gang")
                    _tel_event("worker_exit", rank=rank, gen=self._gen,
                               code=code, kind=kind)
                    self._postmortem("worker_fault", rank=rank,
                                     code=code, classified=kind)
                    self._terminate_all()
                    return GangResult(
                        returncode=code,
                        failed_rank=rank,
                        per_rank={r: q.returncode
                                  for r, q in self._procs.items()},
                        injected_failures=int(code == FAULT_EXIT_CODE),
                    )
            if not running:
                return GangResult(
                    returncode=0,
                    per_rank={r: p.returncode
                              for r, p in self._procs.items()},
                )
            now = time.monotonic()
            if watch_remote and now - last_remote_check >= max(
                    self.monitor_interval_s, 0.2):
                last_remote_check = now
                rep = None
                for attempt in (0, 1):  # one retry: a single RST/timeout
                    try:                # must not consume a restart budget
                        rep = self._rpc_coord(
                            {"op": "status", "gen": self._gen},
                            RPC_TIMEOUT_S)
                        break
                    except (OSError, ValueError):
                        if attempt == 0:
                            time.sleep(0.5)
                if rep is None:
                    rep = {"failed": False, "abort": True, "code": 1}
                    self.log("[launch] coordinator unreachable; "
                             "terminating gang")
                if rep.get("failed") or rep.get("abort"):
                    self.log(f"[launch] remote failure in generation "
                             f"{self._gen}; terminating local workers")
                    self._terminate_all()
                    return GangResult(
                        returncode=rep.get("code") or 1,
                        per_rank={r: q.returncode
                                  for r, q in self._procs.items()},
                    )
            time.sleep(self.monitor_interval_s)

    # -- elastic resize (round 12) ----------------------------------------
    def _heartbeats(self, run_dir: str) -> dict[int, dict]:
        """Read every rank's newest heartbeat: {rank: {"step", "gen",
        "age_s"}}.  Heartbeats are single-JSON files written atomically
        by parallel/elastic.py Heartbeat; unreadable/half-written files
        are skipped (the next beat lands whole)."""
        out: dict[int, dict] = {}
        try:
            names = os.listdir(run_dir)
        except OSError:
            return out
        for name in names:
            if not (name.startswith(HEARTBEAT_PREFIX)
                    and name.endswith(".json")):
                continue
            hb = read_heartbeat(os.path.join(run_dir, name))
            if hb is not None:
                out[hb["rank"]] = hb
        return out

    def _clear_heartbeats(self, run_dir: str) -> None:
        try:
            for name in os.listdir(run_dir):
                if name.startswith(HEARTBEAT_PREFIX):
                    try:
                        os.remove(os.path.join(run_dir, name))
                    except OSError:
                        pass
        except OSError:
            pass

    def _run_elastic(self) -> GangResult:
        """Elastic supervision: worker loss (dead PID, hung heartbeat, or
        a worker-requested resize) within [min, max] costs a generation
        bump — drain survivors at a sync point, re-rendezvous smaller,
        resume from the resharded checkpoint — instead of the job; the
        gang grows back once the lost slot is eligible again and the
        shrunk gang has provably advanced."""
        cfg = self.elastic
        run_dir = cfg.run_dir or tempfile.mkdtemp(prefix="elastic_gang_")
        os.makedirs(run_dir, exist_ok=True)
        size = cfg.max_workers
        lost_at: list[float] = []   # when each currently-lost slot died
        injected = 0
        events: list[dict] = []

        def finish(code: int, failed_rank=None, per_rank=None) -> GangResult:
            return GangResult(
                returncode=code, failed_rank=failed_rank,
                restarts_used=self._gen,
                per_rank=per_rank if per_rank is not None else
                {r: p.returncode for r, p in self._procs.items()},
                injected_failures=injected, resize_events=events)

        while True:
            self._clear_heartbeats(run_dir)
            self._procs = {}
            self._spawn(size, extra_env={
                ELASTIC_DIR_ENV: run_dir,
                ELASTIC_MIN_ENV: str(cfg.min_workers),
                ELASTIC_MAX_ENV: str(cfg.max_workers),
            })
            try:
                kind, info = self._monitor_elastic(run_dir, size, lost_at)
            except BaseException:
                # Ctrl-C / SIGTERM to the agent: workers still get the
                # CONFIGURED drain window to flush their checkpoint (an
                # operator who set --drain-grace 60 for slow saves must
                # not have teardown SIGKILL them at the 10 s default)
                self._terminate_all(grace_s=cfg.drain_grace_s)
                raise
            if kind == "done":
                return finish(0, per_rank=info)
            if kind == "grow":
                n_back = info
                self.log(f"[launch] elastic: {n_back} lost slot(s) "
                         f"rejoining; draining gang of {size} to grow to "
                         f"{size + n_back}")
                self._terminate_all(grace_s=cfg.drain_grace_s)
                events.append({"gen": self._gen, "kind": "grow",
                               "from_size": size, "to_size": size + n_back,
                               "reason": "rejoin", "rank": None})
                _tel_event("gang_resize", **events[-1])
                size += n_back
                del lost_at[:n_back]
                self._gen += 1
                continue
            # kind == "lost": a worker died / hung / requested a resize.
            # Shrink by exactly the ONE lost slot: survivors may be mid-
            # collective with the dead peer and exit messily during the
            # drain (a broken psum is a symptom, not a second loss) —
            # every slot respawns fresh at the new world size anyway.
            rank, code, reason = info
            injected += int(code == FAULT_EXIT_CODE)
            new_size = size - 1
            shrinks = sum(1 for e in events if e["kind"] == "shrink")
            if shrinks >= cfg.max_resizes:
                self.log(f"[launch] elastic: rank {rank} lost ({reason}) "
                         f"after {shrinks} shrinks — resize budget "
                         f"max_resizes={cfg.max_resizes} exhausted; "
                         f"terminating gang")
                self._terminate_all(grace_s=cfg.drain_grace_s)
                return finish(code or 1, failed_rank=rank)
            if new_size < cfg.min_workers:
                self.log(f"[launch] elastic: rank {rank} lost ({reason}) "
                         f"leaves {new_size} < min_nodes="
                         f"{cfg.min_workers}; terminating gang")
                self._terminate_all(grace_s=cfg.drain_grace_s)
                return finish(code or 1, failed_rank=rank)
            self.log(f"[launch] elastic: rank {rank} lost ({reason}); "
                     f"draining survivors and resharding to world size "
                     f"{new_size}")
            self._terminate_all(grace_s=cfg.drain_grace_s)
            events.append({"gen": self._gen, "kind": "shrink",
                           "from_size": size, "to_size": new_size,
                           "reason": reason, "rank": rank})
            _tel_event("gang_resize", **events[-1])
            self._postmortem("elastic_shrink", size=new_size,
                             **{k: v for k, v in events[-1].items()
                                if k != "kind"})
            lost_at.append(time.monotonic())
            size = new_size
            self._gen += 1

    def _monitor_elastic(self, run_dir: str, size: int,
                         lost_at: list[float]):
        """Supervise one elastic generation.  Returns one of
        ("done", per_rank), ("lost", (rank, code, reason)), or
        ("grow", n_slots_rejoining)."""
        cfg = self.elastic
        gen_start_step: dict[int, int] = {}   # rank -> first hb step seen
        last_step: dict[int, int] = {}
        while True:
            per_rank: dict[int, int] = {}
            running = []
            for rank, p in self._procs.items():
                code = p.poll()
                per_rank[rank] = code
                if code is None:
                    running.append(rank)
                elif code == ELASTIC_RESIZE_EXIT_CODE:
                    self.log(f"[launch] rank {rank} requested a gang "
                             f"resize (exit {code})")
                    _tel_event("worker_exit", rank=rank, gen=self._gen,
                               code=code, kind="requested resize")
                    return "lost", (rank, 0, "requested")
                elif code not in (0,):
                    kind = ("injected fault" if code == FAULT_EXIT_CODE
                            else "failure")
                    self.log(f"[launch] rank {rank} FAILED with exit code "
                             f"{code} ({kind})")
                    _tel_event("worker_exit", rank=rank, gen=self._gen,
                               code=code, kind=kind)
                    self._postmortem("worker_fault", size=size,
                                     rank=rank, code=code,
                                     classified=kind)
                    return "lost", (rank, code, kind)
            if not running:
                return "done", per_rank
            # heartbeat staleness: one shared verdict (heartbeat_verdict
            # — the fleet router judges its replicas through the same
            # helper).  "cold" ranks (no beat this generation — still
            # compiling) are ineligible; their PID liveness is already
            # covered by the poll() loop above, so pid=None here.
            beats = self._heartbeats(run_dir)
            for rank in running:
                hb = beats.get(rank)
                verdict = heartbeat_verdict(
                    hb, stale_s=cfg.heartbeat_timeout_s, gen=self._gen)
                if verdict == "cold":
                    continue
                gen_start_step.setdefault(rank, hb["step"])
                last_step[rank] = hb["step"]
                if verdict == "stale":
                    self.log(f"[launch] rank {rank} heartbeat stale "
                             f"({hb['age_s']:.1f}s > "
                             f"{cfg.heartbeat_timeout_s}s); killing hung "
                             f"worker")
                    _tel_event("heartbeat_stale", rank=rank,
                               gen=self._gen, age_s=hb["age_s"],
                               timeout_s=cfg.heartbeat_timeout_s)
                    self._postmortem("worker_fault", size=size,
                                     rank=rank, code=None,
                                     classified="heartbeat_stale",
                                     age_s=hb["age_s"])
                    try:
                        self._procs[rank].kill()
                    except OSError:
                        pass
                    self._procs[rank].wait()
                    return "lost", (rank, 1, "heartbeat")
            # grow back: lost slots past the rejoin delay, once every
            # live rank's heartbeat advanced grow_after_steps in-gen
            if size < cfg.max_workers and lost_at:
                now = time.monotonic()
                eligible = sum(1 for t in lost_at
                               if now - t >= cfg.rejoin_delay_s)
                eligible = min(eligible, cfg.max_workers - size)
                # every still-RUNNING rank must have beaten this gen and
                # advanced enough (ranks that finished and exited 0 no
                # longer gate growth; a rank still compiling does)
                advanced = bool(running) and all(
                    r in last_step
                    and last_step[r] - gen_start_step[r]
                    >= cfg.grow_after_steps
                    for r in running)
                if eligible > 0 and advanced:
                    return "grow", eligible
            time.sleep(self.monitor_interval_s)

    # -- gang orchestration -------------------------------------------------
    def _rpc_coord(self, msg: dict, timeout: float) -> dict:
        return _rpc(self.master_addr, self.agent_port, msg, timeout)

    def _barrier(self, gen: int) -> bool:
        """Arrive at generation ``gen``; True when every current member
        is in.  The node-0 coordinator may come up after us — retry the
        dial.  The reply's membership (round 19: the barrier counts
        CHANGING membership, not a fixed nnodes) is recorded so this
        generation spawns against the world size it rendezvoused at."""
        deadline = time.monotonic() + CONNECT_RETRY_S
        while True:
            try:
                rep = self._rpc_coord(
                    {"op": "barrier", "node": self.node_rank, "gen": gen},
                    BARRIER_TIMEOUT_S + RPC_TIMEOUT_S)
                ws = rep.get("world_size")
                if ws:
                    self._barrier_world = int(ws)
                    if ws != self.nnodes:
                        self.log(f"[launch] generation {gen} rendezvoused "
                                 f"at world size {ws} (membership "
                                 f"changed from {self.nnodes})")
                return bool(rep.get("ok"))
            except (OSError, ValueError):
                if time.monotonic() > deadline:
                    return False
                time.sleep(0.2)

    def run(self) -> GangResult:
        """Run the gang, restarting up to ``max_restarts`` times on failure.

        Single node: plain supervise-and-restart.  Multi node: every
        (re)start passes a coordinator barrier per generation, so all nodes
        always run the same generation (see module docstring).  Elastic
        mode (an ``ElasticConfig``): resize instead of restart — worker
        loss within [min, max] shrinks the gang at a drain boundary; the
        lost slot growing back is the same machinery in reverse.
        """
        if self.elastic is not None:
            result = self._run_elastic()
        elif self.nnodes == 1:
            result = self._run_local()
        else:
            result = self._run_coordinated()
        result.drain = dict(self._drain_stats)
        return result

    def _run_local(self) -> GangResult:
        attempt = 0
        injected = 0
        while True:
            self._gen = attempt
            self._procs = {}
            self._spawn()
            try:
                result = self._monitor()
            except BaseException:
                # Ctrl-C, SIGTERM (via the main() handler), or any agent
                # crash: never leave workers orphaned on the chips.
                self._terminate_all()
                raise
            injected += result.injected_failures
            result.injected_failures = injected
            result.restarts_used = attempt
            if result.returncode == 0 or attempt >= self.max_restarts:
                return result
            attempt += 1
            self.log(f"[launch] restarting gang (attempt {attempt}/"
                     f"{self.max_restarts})")

    def _send(self, msg: dict) -> None:
        """Best-effort coordinator notification."""
        try:
            self._rpc_coord(msg, RPC_TIMEOUT_S)
        except (OSError, ValueError):
            pass

    def _run_coordinated(self) -> GangResult:
        coord = (_Coordinator(self.nnodes, self.agent_port)
                 if self.node_rank == 0 else None)
        try:
            gen = 0
            injected = 0
            last: GangResult | None = None
            while True:
                self._gen = gen
                if not self._barrier(gen):
                    # Denied: another node settled (done/abort) or the
                    # rendezvous timed out.  Report the real failure that
                    # got us here, not a synthetic code.
                    self.log(f"[launch] rendezvous for generation {gen} "
                             f"denied (done/abort/timeout)")
                    return last or GangResult(returncode=1)
                self._procs = {}
                self._spawn()
                try:
                    result = self._monitor(watch_remote=True)
                except BaseException:
                    self._terminate_all()
                    raise
                injected += result.injected_failures
                result.injected_failures = injected
                result.restarts_used = gen
                if result.returncode == 0:
                    # No further generations for laggards — but running
                    # peers finishing this generation are NOT torn down.
                    return result
                last = result
                self._send({"op": "fail", "gen": gen,
                            "code": result.returncode})
                if gen >= self.max_restarts:
                    self._send({"op": "abort"})
                    return result
                gen += 1
                self.log(f"[launch] restarting gang, generation {gen}/"
                         f"{self.max_restarts}")
        finally:
            # Settle this node with the coordinator no matter how we exit,
            # then (node 0) keep the coordinator alive until every node has
            # settled — a vanished coordinator reads as a remote failure to
            # peers still polling.
            self._send({"op": "done", "node": self.node_rank})
            if coord is not None:
                if not coord.wait_all_finished(BARRIER_TIMEOUT_S):
                    self.log("[launch] not all nodes settled before "
                             "coordinator shutdown")
                coord.close()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_pytorch_tpu.launch",
        description="torchrun-style launcher (reference start_ddp.sh:1) "
                    "with failure detection",
    )
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node-rank", "--node_rank", type=int, default=0)
    p.add_argument("--nproc-per-node", "--nproc_per_node", type=int,
                   default=1,
                   help="processes on this node (TPU: 1 per host owns all "
                        "local chips; >1 is for CPU simulation)")
    p.add_argument("--master-addr", "--master_addr", default="127.0.0.1")
    p.add_argument("--master-port", "--master_port", type=int,
                   default=DEFAULT_PORT)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="elastic restarts of the whole gang on worker "
                        "failure (torchrun leaves this 0 too, but the "
                        "reference never sets it — start_ddp.sh:1)")
    p.add_argument("--monitor-interval", type=float, default=0.1,
                   help="seconds between worker liveness polls")
    p.add_argument("--agent-port", type=int, default=None,
                   help="coordinator port for multi-node restarts "
                        "(default master_port+1; node 0 hosts)")
    # elastic resize (round 12): detect worker loss, shrink the gang at a
    # drain boundary, reshard from checkpoint, keep training; grow back
    # when the slot rejoins.
    p.add_argument("--elastic", action="store_true",
                   help="resize instead of restart: a worker loss within "
                        "[--min-nodes, --max-nodes] drains the survivors "
                        "at a sync point and re-rendezvouses one smaller "
                        "(resuming from the resharded checkpoint); the "
                        "gang grows back when the slot rejoins")
    p.add_argument("--min-nodes", type=int, default=1,
                   help="elastic: smallest world size worth training at "
                        "(fewer survivors fails the gang)")
    p.add_argument("--max-nodes", type=int, default=None,
                   help="elastic: largest world size (default "
                        "--nproc-per-node); the gang starts here and "
                        "grows back to it")
    p.add_argument("--heartbeat-timeout", type=float, default=300.0,
                   help="elastic: a worker whose newest heartbeat is "
                        "older than this is a HUNG straggler — killed "
                        "and treated as lost (workers that never beat "
                        "are judged by PID only)")
    p.add_argument("--drain-grace", type=float, default=30.0,
                   help="elastic: seconds survivors get to reach a sync "
                        "point and flush their checkpoint on SIGTERM "
                        "before SIGKILL")
    p.add_argument("--rejoin-delay", type=float, default=0.0,
                   help="elastic: seconds after a loss before the slot "
                        "is respawn-eligible (grow-back)")
    p.add_argument("--grow-after-steps", type=int, default=1,
                   help="elastic: grow back only after every live "
                        "worker's heartbeat advanced this many steps in "
                        "the shrunk generation")
    p.add_argument("--max-resizes", type=int, default=16,
                   help="elastic: total shrinks the run may absorb "
                        "before the gang is declared failed (grow-backs "
                        "are free) — bounds the shrink/grow oscillation "
                        "a deterministically-crashing slot would "
                        "otherwise drive forever; replaces "
                        "--max-restarts, which elastic mode ignores")
    p.add_argument("--telemetry-dir", default=None,
                   help="unified run telemetry (round 13): the agent "
                        "logs gang lifecycle events (worker start/exit, "
                        "heartbeat staleness, drains, resize "
                        "generations) into this shared run directory "
                        "and exports it to the workers (TELEMETRY_DIR), "
                        "so every rank's JSONL stream merges into ONE "
                        "Chrome trace (scripts/telemetry_summary.py)")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="worker command: a script path or '-m module', "
                        "optionally preceded by '--'")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    setup_logging()
    log = get_logger("launch")
    if args.telemetry_dir:
        # the agent's own events (rank -1, "agent" in the merged trace)
        # plus the worker env contract: every rank's stream lands in the
        # same run directory, one timeline for the whole gang
        telemetry.enable(args.telemetry_dir, rank=-1, gen=0,
                         label="agent")
        os.environ[telemetry.TELEMETRY_DIR_ENV] = args.telemetry_dir
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        build_parser().error("no worker command given")
    elastic = None
    if args.elastic:
        max_workers = (args.max_nodes if args.max_nodes is not None
                       else args.nproc_per_node)
        if (args.max_nodes is not None and args.nproc_per_node != 1
                and args.nproc_per_node != args.max_nodes):
            build_parser().error(
                f"--elastic: --nproc-per-node {args.nproc_per_node} "
                f"conflicts with --max-nodes {args.max_nodes} (the gang "
                f"starts at max-nodes workers; set one, not both)")
        try:
            elastic = ElasticConfig(
                min_workers=args.min_nodes,
                max_workers=max_workers,
                heartbeat_timeout_s=args.heartbeat_timeout,
                drain_grace_s=args.drain_grace,
                rejoin_delay_s=args.rejoin_delay,
                grow_after_steps=args.grow_after_steps,
                max_resizes=args.max_resizes,
            )
        except ValueError as e:
            build_parser().error(str(e))
        args.nproc_per_node = max_workers
    elif args.max_nodes is not None or args.min_nodes != 1:
        build_parser().error(
            "--min-nodes/--max-nodes configure elastic resize; pass "
            "--elastic (or drop the bounds)")
    try:
        agent = LocalAgent(
            cmd,
            nnodes=args.nnodes,
            node_rank=args.node_rank,
            nproc_per_node=args.nproc_per_node,
            master_addr=args.master_addr,
            master_port=args.master_port,
            max_restarts=args.max_restarts,
            monitor_interval_s=args.monitor_interval,
            agent_port=args.agent_port,
            elastic=elastic,
        )
    except ValueError as e:  # e.g. --elastic with --nnodes > 1
        build_parser().error(str(e))
    # A scheduler's SIGTERM must tear down the gang, not orphan it; raising
    # SystemExit routes through run()'s BaseException cleanup.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    result = agent.run()
    # round 13: agent reporting routes through the structured logger
    # (greppable, timestamped, rank-tagged like everything else) instead
    # of bare prints; the per-event telemetry already landed live.
    for ev in result.resize_events:
        log.info("resize: gen %d %s %d -> %d (%s)", ev["gen"], ev["kind"],
                 ev["from_size"], ev["to_size"], ev["reason"])
    if result.drain:
        log.info("drain outcome: %s", result.drain)
    if result.returncode != 0:
        log.error("gang failed: rank %s exit %d after %d restarts",
                  result.failed_rank, result.returncode,
                  result.restarts_used)
    _tel_event("gang_done", returncode=result.returncode,
               restarts_used=result.restarts_used,
               resizes=len(result.resize_events), drain=result.drain)
    telemetry.disable()  # flush before the agent exits
    return result.returncode


if __name__ == "__main__":
    sys.exit(main())
