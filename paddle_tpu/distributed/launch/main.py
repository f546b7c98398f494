"""Launcher implementation: context, collective controller, elastic loop.

Parity map (reference → here):
  launch/context/__init__.py  → Context (arg parsing, env snapshot)
  launch/controllers/collective.py::CollectiveController → PodController
  fleet/elastic/manager.py    → ElasticManager (TCPStore heartbeats, not etcd)
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Context:
    nnodes: int = 1
    node_rank: int = 0
    nproc_per_node: int = 1
    master: Optional[str] = None        # host:port
    job_id: str = "default"
    log_dir: str = "log"
    devices: Optional[str] = None
    max_restart: int = 3
    elastic_timeout_s: float = 30.0
    script: str = ""
    script_args: List[str] = field(default_factory=list)
    run_mode: str = "collective"
    heartbeat_interval: float = 1.0  # seconds; <= 0 disables
    restart_backoff_s: float = 0.5       # base; doubles per restart
    restart_backoff_max_s: float = 60.0  # cap before jitter
    hang_timeout_s: float = 0.0          # stale-rank detector; <=0 off
    engine_dir: Optional[str] = None     # AOT engine bundle for workers
    topology: Optional[str] = None       # mesh spec stamped on telemetry
    straggler_factor: float = 2.0        # fleet skew detector; <=0 off
    straggler_steps: int = 3             # consecutive slow steps to flag
    mitigation: str = "off"              # straggler actuator: off|
    #   exclude|reassign|auto (docs/ROBUSTNESS.md "Mitigation")
    mitigation_cooldown_s: float = 60.0  # min seconds between actions
    pipeline_stages: int = 1             # stage count for reassignment

    @property
    def world_size(self) -> int:
        return self.nnodes * self.nproc_per_node


def parse_args(argv=None) -> Context:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed training job.")
    p.add_argument("--nnodes", type=str, default="1",
                   help="node count; N or MIN:MAX for elastic")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--nproc_per_node", type=int, default=None,
                   help="processes per node (default: 1 — one jax process "
                        "per TPU host)")
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER"),
                   help="host:port of rank-0 coordinator")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--devices", "--gpus", type=str, default=None,
                   help="visible accelerator ids for this pod")
    p.add_argument("--max_restart", type=int, default=3,
                   help="elastic: max pod restarts on failure")
    p.add_argument("--heartbeat_interval", type=float, default=1.0,
                   help="seconds between per-rank heartbeat lines in "
                        "<log_dir>/heartbeat.jsonl (<=0 disables); a "
                        "wedged rank shows up as a pid that stops "
                        "growing its log while staying alive")
    p.add_argument("--restart_backoff", type=float, default=0.5,
                   help="elastic: base seconds of jittered exponential "
                        "backoff between pod restarts (doubles per "
                        "restart; <=0 restarts immediately). A crash "
                        "loop without backoff hammers the coordinator "
                        "and the checkpoint store in lockstep across "
                        "pods")
    p.add_argument("--restart_backoff_max", type=float, default=60.0,
                   help="elastic: backoff cap in seconds (before the "
                        "+/-50%% jitter)")
    p.add_argument("--hang_timeout", type=float, default=0.0,
                   help="stale-heartbeat detector: a rank whose pid is "
                        "alive but whose worker log AND per-rank "
                        "heartbeat file (PADDLE_RANK_HEARTBEAT) stop "
                        "growing for this many seconds is declared "
                        "wedged, SIGKILLed, and recovered through the "
                        "normal elastic restart — hangs become "
                        "restarts. Must exceed the longest legitimate "
                        "silent phase (backend init, compile, restore). "
                        "<=0 disables (an external operator must notice "
                        "the hang)")
    p.add_argument("--engine_dir", type=str,
                   default=os.environ.get("PADDLE_TPU_ENGINE_DIR"),
                   help="AOT engine bundle directory "
                        "(paddle_tpu.inference.aot), exported to every "
                        "rank as PADDLE_TPU_ENGINE_DIR across ALL "
                        "restart epochs — a restarted serving worker "
                        "warm-starts from the bundle (file loads) "
                        "instead of recompiling its programs, which is "
                        "most of the restart MTTR (docs/DEPLOYMENT.md)")
    p.add_argument("--topology", type=str,
                   default=os.environ.get("PADDLE_TPU_TOPOLOGY"),
                   help="mesh spec (e.g. data=4,model=2) exported to "
                        "every rank as PADDLE_TPU_TOPOLOGY: it becomes "
                        "the 'topology' field on every telemetry line "
                        "(docs/OBSERVABILITY.md 'Fleet view'), so a "
                        "directory of rank files names the layout it "
                        "was recorded under")
    p.add_argument("--straggler_factor", type=float, default=2.0,
                   help="fleet straggler detector: flag a rank whose "
                        "step wall time exceeds this multiple of the "
                        "cross-rank median (<=0 disables). Unlike "
                        "--hang_timeout this catches ranks that are "
                        "SLOW but alive — their heartbeat never goes "
                        "silent, so the stale-heartbeat detector is "
                        "structurally blind to them")
    p.add_argument("--straggler_steps", type=int, default=3,
                   help="fleet straggler detector: consecutive "
                        "over-threshold steps before a rank is flagged "
                        "(counted in robustness.stragglers_detected "
                        "and logged with its dominant span)")
    p.add_argument("--mitigation", type=str, default="off",
                   choices=("off", "exclude", "reassign", "auto"),
                   help="straggler MITIGATION actuator: act on the "
                        "fleet detector's persistent-skew incidents "
                        "instead of only logging them. 'exclude' "
                        "kills the slow rank and elastically restarts "
                        "the pod without it (world shrinks, survivors "
                        "resume from the last verified checkpoint); "
                        "'reassign' restarts with a permuted "
                        "stage->device map so the slow rank hosts the "
                        "lightest pipeline stage (needs "
                        "--pipeline_stages > 1); 'auto' prefers "
                        "exclusion and falls back to reassignment. "
                        "Every decision — including holds — is an "
                        "auditable {\"kind\": \"control\"} record in "
                        "<log_dir>/control.jsonl "
                        "(docs/ROBUSTNESS.md 'Mitigation')")
    p.add_argument("--mitigation_cooldown", type=float, default=60.0,
                   help="minimum seconds between mitigation actions — "
                        "a restart's own transient skew (cold caches, "
                        "recompiles) must not trigger a second "
                        "restart")
    p.add_argument("--pipeline_stages", type=int, default=1,
                   help="pipeline stage count the stage-reassignment "
                        "mitigation permutes over (exported to "
                        "workers via PADDLE_TPU_STAGE_MAP on a "
                        "reassign restart)")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    a = p.parse_args(argv)

    nnodes = a.nnodes.split(":")[0]  # MIN of MIN:MAX (elastic range)
    return Context(
        nnodes=int(nnodes), node_rank=a.node_rank,
        nproc_per_node=a.nproc_per_node or 1, master=a.master,
        job_id=a.job_id, log_dir=a.log_dir, devices=a.devices,
        max_restart=a.max_restart, script=a.script,
        script_args=a.script_args,
        heartbeat_interval=a.heartbeat_interval,
        restart_backoff_s=a.restart_backoff,
        restart_backoff_max_s=a.restart_backoff_max,
        hang_timeout_s=a.hang_timeout, engine_dir=a.engine_dir,
        topology=a.topology, straggler_factor=a.straggler_factor,
        straggler_steps=a.straggler_steps, mitigation=a.mitigation,
        mitigation_cooldown_s=a.mitigation_cooldown,
        pipeline_stages=a.pipeline_stages)


def restart_delay(restarts: int, base_s: float, cap_s: float,
                  rng=None) -> float:
    """Jittered exponential backoff for restart N (1-based): base * 2^(N-1),
    capped, with +/-50% jitter so a multi-pod job's restarts decorrelate
    instead of re-stampeding the coordinator in lockstep. ``rng`` is an
    injectable uniform-[0,1) source (tests pin the jitter; the chaos
    harness runs clock-driven instead of sleeping through it)."""
    if base_s <= 0 or restarts <= 0:
        return 0.0
    if rng is None:
        import random
        rng = random.random
    return min(cap_s, base_s * (2 ** (restarts - 1))) \
        * (0.5 + rng())


class PodController:
    """Spawns and babysits this node's worker processes (one 'pod').

    ``exclude`` names GLOBAL ranks evicted by a mitigation
    (exclude-and-restart): their slots are simply not spawned. The
    surviving workers keep their ORIGINAL rank ids — checkpoint
    directories, telemetry/heartbeat file names, and the fleet join
    all key on the rank, and renumbering mid-job would orphan every
    one of them — while ``WORLD_SIZE`` shrinks to the live count and
    ``PADDLE_TPU_EXCLUDED_RANKS`` names the holes."""

    def __init__(self, ctx: Context, exclude=(), stage_map=None):
        self.ctx = ctx
        self.exclude = frozenset(int(r) for r in exclude)
        self.stage_map = list(stage_map) if stage_map else None
        self.procs: List[subprocess.Popen] = []
        self.local_ranks: List[int] = []   # procs[i] runs local rank
        self.logs = []

    def _live_world(self) -> int:
        return self.ctx.world_size - len(self.exclude)

    def _rank_env(self, local_rank: int, restart_epoch: int) -> dict:
        ctx = self.ctx
        rank = ctx.node_rank * ctx.nproc_per_node + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "RANK": str(rank),
            "PADDLE_TRAINERS_NUM": str(self._live_world()),
            "WORLD_SIZE": str(self._live_world()),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "LOCAL_RANK": str(local_rank),
            "PADDLE_JOB_ID": ctx.job_id,
            "PADDLE_RESTART_EPOCH": str(restart_epoch),
            # per-rank worker heartbeat: instrumented workers (Trainer,
            # bench) beat phase/step lines here; silence while the pid
            # stays alive is what the stale-heartbeat detector reads
            "PADDLE_RANK_HEARTBEAT": self._hb_path(rank),
            "PADDLE_RANK_HEARTBEAT_INTERVAL": str(
                ctx.heartbeat_interval if ctx.heartbeat_interval > 0
                else 1.0),
            # per-rank telemetry: every worker gets its OWN JSONL sink
            # beside the heartbeat files — deterministic names the
            # fleet aggregator and tools/fleet_report.py glob. This
            # deliberately overrides a launcher-level
            # PADDLE_TPU_TELEMETRY_JSONL: N ranks appending to one
            # shared file is interleaved corruption, which the fleet
            # view exists to replace (docs/OBSERVABILITY.md)
            "PADDLE_TPU_TELEMETRY_JSONL": self._telemetry_path(rank),
        })
        if self.exclude:
            env["PADDLE_TPU_EXCLUDED_RANKS"] = ",".join(
                str(r) for r in sorted(self.exclude))
        if self.stage_map:
            # reassign_stages mitigation: the permuted stage->device
            # map every worker's mesh build consumes
            # (distributed.mesh._apply_stage_map)
            env["PADDLE_TPU_STAGE_MAP"] = ",".join(
                str(g) for g in self.stage_map)
        if ctx.topology:
            # stamped onto every telemetry line via rank_identity()
            env["PADDLE_TPU_TOPOLOGY"] = ctx.topology
        if ctx.engine_dir:
            # every restart epoch warm-starts from the same AOT bundle
            # (inference.aot.warm_start reads this by default): restart
            # cost is file loads, not recompiles
            env["PADDLE_TPU_ENGINE_DIR"] = os.path.abspath(
                ctx.engine_dir)
        if ctx.master:
            env["PADDLE_MASTER"] = ctx.master
            host, port = ctx.master.rsplit(":", 1)
            env.setdefault("MASTER_ADDR", host)
            env.setdefault("MASTER_PORT", port)
        if ctx.devices is not None:
            # parity with FLAGS_selected_gpus; on TPU selects chip subsets
            env["FLAGS_selected_devices"] = ctx.devices
            env["TPU_VISIBLE_DEVICES"] = ctx.devices
        return env

    def start(self, restart_epoch: int = 0):
        ctx = self.ctx
        os.makedirs(ctx.log_dir, exist_ok=True)
        self.procs, self.local_ranks, self.logs = [], [], []
        for lr in range(ctx.nproc_per_node):
            rank = ctx.node_rank * ctx.nproc_per_node + lr
            if rank in self.exclude:
                continue
            log_path = os.path.join(ctx.log_dir, f"workerlog.{lr}")
            logf = open(log_path, "ab")
            cmd = [sys.executable, "-u", ctx.script] + ctx.script_args
            proc = subprocess.Popen(cmd, env=self._rank_env(lr,
                                                            restart_epoch),
                                    stdout=logf, stderr=subprocess.STDOUT)
            self.procs.append(proc)
            self.local_ranks.append(lr)
            self.logs.append(logf)

    def poll(self) -> Optional[int]:
        """None while all alive; else the first non-None returncode
        (0 only when ALL exited 0)."""
        codes = [p.poll() for p in self.procs]
        if any(c not in (0, None) for c in codes):
            return next(c for c in codes if c not in (0, None))
        if all(c == 0 for c in codes):
            return 0
        return None

    def stop(self, sig=signal.SIGTERM, grace_s: float = 10.0):
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except ProcessLookupError:
                    pass
        deadline = time.time() + grace_s
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for f in self.logs:
            f.close()

    def rank_states(self) -> List[dict]:
        """Per-rank liveness snapshot for the heartbeat: a rank whose
        pid is alive but whose log stopped growing is the wedged-rank
        signature."""
        out = []
        for lr, p in zip(self.local_ranks, self.procs):
            path = os.path.join(self.ctx.log_dir, f"workerlog.{lr}")
            try:
                log_bytes = os.path.getsize(path)
            except OSError:
                log_bytes = 0
            rank = self.ctx.node_rank * self.ctx.nproc_per_node + lr
            try:
                hb_bytes = os.path.getsize(self._hb_path(rank))
            except OSError:
                hb_bytes = 0
            rc = p.poll()  # once: alive/returncode must agree
            out.append({"rank": rank, "local_rank": lr, "pid": p.pid,
                        "alive": rc is None, "returncode": rc,
                        "log_bytes": log_bytes, "hb_bytes": hb_bytes})
        return out

    def _hb_path(self, rank: int) -> str:
        return os.path.join(os.path.abspath(self.ctx.log_dir),
                            f"heartbeat_rank{rank}.jsonl")

    def _telemetry_path(self, rank: int) -> str:
        return os.path.join(os.path.abspath(self.ctx.log_dir),
                            f"telemetry_rank{rank}.jsonl")

    def kill_rank(self, local_rank: int):
        """SIGKILL one wedged worker (SIGTERM would be swallowed by a
        rank stuck inside a native call); poll() then reports the pod
        failed and the normal elastic restart path takes over."""
        try:
            p = self.procs[self.local_ranks.index(local_rank)]
        except ValueError:
            return  # excluded or never spawned this epoch
        if p.poll() is None:
            try:
                p.kill()
            except ProcessLookupError:
                pass

    def last_phase(self, rank: int) -> Optional[dict]:
        """The wedged rank's last self-reported heartbeat record (phase/
        step/ts) from its per-rank heartbeat file — names WHERE it hung
        in the restart log instead of just 'it stopped'."""
        try:
            path = self._hb_path(rank)
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(max(0, size - 4096))
                lines = f.read().decode(errors="replace").splitlines()
        except OSError:
            return None
        import json
        for line in reversed(lines):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "heartbeat":
                return rec
        return None

    def tail_logs(self, n: int = 20):
        for lr in self.local_ranks:
            path = os.path.join(self.ctx.log_dir, f"workerlog.{lr}")
            try:
                with open(path, "rb") as f:
                    lines = f.read().decode(errors="replace").splitlines()
                for line in lines[-n:]:
                    print(f"[rank {lr}] {line}", file=sys.stderr)
            except OSError:
                pass


class HangDetector:
    """Stale-heartbeat detection over PodController.rank_states snapshots.

    A wedged rank — stuck collective, stalled data loader, hung backend
    init (the failure that killed bench rounds r01–r05) — keeps its pid
    alive, so exit-code babysitting never fires. Its *signature* is
    silence: the worker log and the per-rank heartbeat file both stop
    growing. Feed ``observe()`` liveness snapshots; a rank whose
    progress fingerprint (log_bytes, hb_bytes) is unchanged for
    ``timeout_s`` while alive is returned as wedged. Any fingerprint
    change (or restart of the rank's pid) resets its clock. Pure state
    machine with an injectable clock — tests drive it with fake
    snapshots and fake time, no real sleeps."""

    def __init__(self, timeout_s: float, now_fn=time.time):
        self.timeout_s = float(timeout_s)
        self._now = now_fn
        # rank -> (fingerprint, last_change_ts); the fingerprint is
        # (pid, log_bytes, hb_bytes)
        self._seen: dict = {}

    def observe(self, rank_states: List[dict], now: Optional[float] = None) \
            -> List[dict]:
        """One snapshot in, currently-wedged rank states out."""
        now = self._now() if now is None else now
        wedged = []
        for st in rank_states:
            rank = st.get("rank")
            if not st.get("alive"):
                self._seen.pop(rank, None)
                continue
            fp = (st.get("pid"), st.get("log_bytes", 0),
                  st.get("hb_bytes", 0))
            prev = self._seen.get(rank)
            if prev is None or prev[0] != fp:
                self._seen[rank] = (fp, now)
            elif self.timeout_s > 0 and now - prev[1] >= self.timeout_s:
                wedged.append(st)
        return wedged

    def silence_s(self, rank, now: Optional[float] = None) -> float:
        """How long this rank has been silent (0 if unseen)."""
        now = self._now() if now is None else now
        prev = self._seen.get(rank)
        return (now - prev[1]) if prev else 0.0

    def forget(self, rank):
        self._seen.pop(rank, None)


class ElasticManager:
    """Pod membership + heartbeat over TCPStore (parity: etcd-based
    fleet/elastic/manager.py). Node 0 hosts the store next to the master
    port; each pod registers and heartbeats; a missed heartbeat or child
    failure triggers a pod-wide restart (from the user's checkpoint)."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.store = None
        if ctx.master and ctx.nnodes > 1:
            from ..._native import TCPStore, available
            if available():
                host, port = ctx.master.rsplit(":", 1)
                self.store = TCPStore(host, int(port) + 2,
                                      is_master=(ctx.node_rank == 0),
                                      world_size=ctx.nnodes)

    def register(self, epoch: int):
        if self.store:
            self.store.set(f"elastic/{self.ctx.job_id}/pod{self.ctx.node_rank}",
                           str(epoch))
            self.store.barrier(f"epoch{epoch}", self.ctx.nnodes)

    def heartbeat(self):
        if self.store:
            self.store.add(
                f"elastic/{self.ctx.job_id}/hb{self.ctx.node_rank}", 1)

    # -- pod-wide restart coordination ----------------------------------
    # A failed node raises a per-epoch restart flag; healthy nodes poll
    # it and tear down their (still running) pods so every node advances
    # to epoch+1 and re-enters the barrier together. Without this
    # broadcast, only the failed node would loop and the barrier would
    # hang. The flag is an add()-based counter keyed BY epoch, so
    # concurrent failures in the same epoch are idempotent (any value
    # > 0 means "everyone moves to epoch+1") — no read-modify-write race.
    def _req_key(self, epoch: int):
        return f"elastic/{self.ctx.job_id}/restart_req/{epoch}"

    def restart_requested(self, epoch: int) -> bool:
        if not self.store:
            return False
        return self.store.add(self._req_key(epoch), 0) > 0

    def request_restart(self, epoch: int):
        if self.store:
            self.store.add(self._req_key(epoch), 1)

    def close(self):
        if self.store:
            self.store.close()


def launch(ctx: Context, now_fn=time.time, sleep_fn=time.sleep,
           rng=None) -> int:
    """Run the pod until success, failure, or restart budget exhausted.

    ``now_fn``/``sleep_fn``/``rng`` make every launcher timing path —
    fleet/detector polling cadence, recovery MTTR stamps, and the
    jittered restart backoff — clock-injectable, so chaos tests drive
    the babysit loop with a fake clock instead of sleeping through
    real backoff windows."""
    from ...observability import RankHeartbeat, tracing as _tr
    from ...observability import metrics as _obsm
    from ...observability.fleet import FleetAggregator
    elastic = ElasticManager(ctx)
    hb = RankHeartbeat(os.path.join(ctx.log_dir, "heartbeat.jsonl"),
                       interval=ctx.heartbeat_interval)
    os.makedirs(ctx.log_dir, exist_ok=True)
    # straggler mitigation actuator (docs/ROBUSTNESS.md "Mitigation"):
    # consumes the fleet detector's incidents, decides exclude/reassign/
    # hold under cooldown + flap damping, and audits EVERY decision to
    # <log_dir>/control.jsonl; this loop executes what it decides
    mit = None
    mit_pending: List[dict] = []    # comm-wait-inversion incidents
    mit_consumed = 0                # fleet.stragglers read cursor
    if ctx.mitigation != "off":
        from .mitigate import MitigationController
        control_path = os.path.join(ctx.log_dir, "control.jsonl")

        def _emit_control(rec, _path=control_path):
            import json
            with open(_path, "a") as f:
                f.write(json.dumps(rec) + "\n")

        mit = MitigationController(
            world_size=ctx.world_size, mode=ctx.mitigation,
            num_stages=ctx.pipeline_stages,
            cooldown_s=ctx.mitigation_cooldown_s,
            now_fn=now_fn, emit=_emit_control)

    def _on_step(step, durs, share):
        # fleet-joined step stats feed the mitigation cost model and
        # its comm-wait-inversion detector (a synchronous straggler
        # shows NO dur skew — the others absorb it as comm-wait)
        if mit is None:
            return
        inc = mit.note_step(step, durs, share, now=now_fn())
        if inc is not None:
            mit_pending.append(inc)

    # fleet view: tail every rank's telemetry/heartbeat file, join
    # train.step spans on the global step index, flag persistent
    # stragglers (slow-but-alive ranks the stale-heartbeat detector
    # cannot see) — docs/OBSERVABILITY.md "Fleet view"
    # expected_ranks is the LOCAL worker count: this node's log_dir
    # only ever holds this pod's rank files (multi-node jobs get one
    # aggregator per node, each joining its own pod's ranks)
    fleet = FleetAggregator(ctx.log_dir,
                            straggler_factor=ctx.straggler_factor,
                            straggler_steps=ctx.straggler_steps,
                            expected_ranks=ctx.nproc_per_node,
                            now_fn=now_fn, on_step=_on_step)
    fleet_interval = max(0.25, min(1.0, ctx.heartbeat_interval))
    next_fleet = 0.0
    det = HangDetector(ctx.hang_timeout_s, now_fn=now_fn) \
        if ctx.hang_timeout_s > 0 else None
    det_interval = max(0.2, min(1.0, ctx.hang_timeout_s / 4.0)) \
        if det is not None else 0.0
    next_det = 0.0
    recovery = None   # open incident: {"t": detect_ts, "span": ...}
    rc = 1
    epoch = 0
    restarts = 0

    def finish_recovery(status: str, via=None):
        nonlocal recovery
        if recovery is None:
            return
        mttr = now_fn() - recovery["t"]
        if status == "ok":
            # the recovery-time SLO: incident declared (hang detected
            # OR mitigation triggered) -> restarted rank observably
            # making progress again
            _obsm.gauge("robustness.mttr_seconds", unit="s").set(mttr)
            print(f"[launch] recovered {mttr:.2f}s after incident "
                  f"detection (MTTR; first progress from rank {via})",
                  file=sys.stderr)
        recovery["span"].end(status=status, mttr_s=round(mttr, 3))
        recovery = None

    try:
        while True:
            # one span per restart epoch: the elastic trajectory of a
            # crash-looping job reads straight out of the trace
            ep_sp = _tr.start_span("launch.epoch", parent=None,
                                   epoch=epoch, restarts=restarts,
                                   node=ctx.node_rank)
            elastic.register(epoch)
            pod = PodController(
                ctx,
                exclude=(mit.excluded if mit is not None else ()),
                stage_map=(mit.stage_map if mit is not None else None))
            pod.start(restart_epoch=epoch)
            # post-restart progress baseline: logs/heartbeats append
            # across epochs, so "recovered" = any alive rank's files
            # growing past their size at this epoch's start
            baseline = {st["rank"]: (st["log_bytes"], st["hb_bytes"])
                        for st in pod.rank_states()} \
                if recovery is not None else None
            peer_restart = False
            try:
                while True:
                    rc = pod.poll()
                    if rc is not None:
                        break
                    if elastic.restart_requested(epoch):
                        peer_restart = True
                        break
                    elastic.heartbeat()
                    if now_fn() >= next_fleet:
                        next_fleet = now_fn() + fleet_interval
                        try:
                            fleet.poll()
                        except Exception:
                            # observability must never kill the pod
                            # supervision that hosts it
                            pass
                        if mit is not None:
                            incidents = list(
                                fleet.stragglers[mit_consumed:])
                            mit_consumed = len(fleet.stragglers)
                            incidents.extend(mit_pending)
                            mit_pending.clear()
                            for inc in incidents:
                                dec = mit.offer(inc, now=now_fn())
                                act = dec.get("action")
                                if act not in ("exclude_restart",
                                               "reassign_stages"):
                                    continue
                                mrank = int(dec["params"]["rank"])
                                ep_sp.event("mitigation", action=act,
                                            rank=mrank,
                                            rule=dec.get("rule"))
                                print(
                                    f"[launch] mitigation: {act} rank "
                                    f"{mrank} (seq {dec.get('seq')}; "
                                    "restarting pod — see "
                                    "control.jsonl)", file=sys.stderr)
                                if recovery is None:
                                    recovery = {
                                        "t": now_fn(),
                                        "span": _tr.start_span(
                                            "launch.recovery",
                                            parent=None, rank=mrank,
                                            phase="mitigation",
                                            action=act)}
                                if act == "exclude_restart":
                                    # stop joining on the evicted
                                    # rank's files: it will never
                                    # report another step
                                    fleet.retire_rank(str(mrank))
                                if det is not None:
                                    det.forget(mrank)
                                lr = mrank \
                                    - ctx.node_rank * ctx.nproc_per_node
                                if 0 <= lr < ctx.nproc_per_node:
                                    # the kill surfaces as a pod
                                    # failure; the elastic restart
                                    # re-spawns with the new
                                    # exclude/stage_map
                                    pod.kill_rank(lr)
                    states = None
                    if hb.due():  # rank_states stats N files: build it
                        states = pod.rank_states()
                        hb.beat(node=ctx.node_rank, epoch=epoch,  # 1x per
                                restarts=restarts,                # interval
                                ranks=states)
                    if baseline is not None:
                        # recovery closes on first observed progress —
                        # runs with or without the hang detector (a
                        # mitigation restart must close its MTTR
                        # window even when --hang_timeout is off)
                        if states is None:
                            states = pod.rank_states()
                        for st in states:
                            base = baseline.get(st["rank"], (0, 0))
                            if st["alive"] and (
                                    st["log_bytes"] > base[0]
                                    or st["hb_bytes"] > base[1]):
                                finish_recovery("ok", via=st["rank"])
                                baseline = None
                                break
                    if (det is not None
                            and now_fn() >= next_det):
                        next_det = now_fn() + det_interval
                        if states is None:
                            states = pod.rank_states()
                        for st in det.observe(states):
                            phase = pod.last_phase(st["rank"]) or {}
                            silent = det.silence_s(st["rank"])
                            print(
                                f"[launch] rank {st['rank']} wedged: pid "
                                f"{st['pid']} alive but no log/heartbeat "
                                f"progress for {silent:.1f}s (last phase "
                                f"{phase.get('phase')!r}"
                                + (f", step {phase.get('step')}"
                                   if phase.get("step") is not None
                                   else "")
                                + "); SIGKILL — the hang becomes a "
                                  "restart", file=sys.stderr)
                            _obsm.counter(
                                "robustness.hangs_detected").inc()
                            ep_sp.event("hang_detected",
                                        rank=st["rank"], pid=st["pid"],
                                        silent_s=round(silent, 2),
                                        phase=phase.get("phase"),
                                        step=phase.get("step"))
                            if recovery is None:
                                recovery = {
                                    "t": now_fn(),
                                    "span": _tr.start_span(
                                        "launch.recovery", parent=None,
                                        rank=st["rank"],
                                        phase=phase.get("phase"))}
                            det.forget(st["rank"])
                            pod.kill_rank(st["local_rank"])
                    sleep_fn(0.2)
            except KeyboardInterrupt:
                pod.stop(signal.SIGINT)
                ep_sp.end(status="interrupted")
                finish_recovery("interrupted")
                return 130
            if not peer_restart and rc == 0:
                # success is only final if no peer failed concurrently —
                # otherwise join the restart so the peers' epoch barrier
                # (and, on node 0, the store we host) stays alive
                if not elastic.restart_requested(epoch):
                    ep_sp.end(status="ok")
                    # a silent worker can run to completion between
                    # detector ticks: success IS recovery
                    finish_recovery("ok", via="pod_exit")
                    return 0
                peer_restart = True
            restarts += 1  # counted identically on every node
            if peer_restart:
                ep_sp.event("peer_restart")
                print("[launch] peer pod failed, joining pod-wide restart "
                      f"{restarts}/{ctx.max_restart}", file=sys.stderr)
            else:
                ep_sp.event("pod_exit", rc=rc)
                print(f"[launch] pod failed (exit {rc}), restart "
                      f"{restarts}/{ctx.max_restart}", file=sys.stderr)
                pod.tail_logs()
                elastic.request_restart(epoch)
            pod.stop()
            if restarts > ctx.max_restart:
                ep_sp.end(status="failed")
                finish_recovery("failed")
                # budget exhausted: leave the epoch/restart trajectory
                # on disk next to the worker logs
                _tr.flight_dump(
                    path=os.path.join(ctx.log_dir,
                                      f"flight_{os.getpid()}.json"),
                    reason="restart_budget_exhausted")
                break
            ep_sp.end(status="restart")
            delay = restart_delay(restarts, ctx.restart_backoff_s,
                                  ctx.restart_backoff_max_s, rng=rng)
            if delay > 0:
                print(f"[launch] backing off {delay:.2f}s before restart "
                      f"epoch {epoch + 1} (restart {restarts}/"
                      f"{ctx.max_restart})", file=sys.stderr)
                sleep_fn(delay)
            epoch += 1
        return rc if rc is not None else 1
    finally:
        try:
            fleet.poll()    # drain what workers wrote just before exit
        except Exception:
            pass
        fleet.close()
        hb.close()
        elastic.close()


def main(argv=None) -> int:
    ctx = parse_args(argv)
    code = launch(ctx)
    if argv is None:
        sys.exit(code)
    return code
