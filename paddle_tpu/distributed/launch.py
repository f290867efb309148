"""`python -m paddle_tpu.distributed.launch` — multi-host job launcher.

Reference: /root/reference/python/paddle/distributed/fleet/launch.py —
`launch_collective` (:198) spawns per-device worker subprocesses with the
PADDLE_* env contract and watches them; `launch_ps` (:248) starts
pserver+trainer processes for parameter-server mode.

TPU mapping: one worker process per host of the slice (`--nproc_per_node`
defaults to 1 — a single jax client drives all local chips); `--ips` lists
slice hosts; rank-0 endpoint doubles as the jax.distributed coordinator.

The launcher itself stays OFF JAX: it imports the package (which
initialises no backend) and never calls `jax.devices()` or runs a
computation.  A chip belongs to one process at a time — a parent that had
touched JAX would hold it, and the trainer it spawns would fail or hang.
Keep it so (`tests/test_chip_smoke.py` checks the import stays cold).

Supervision (docs/elastic.md): the launcher is a SUPERVISOR, not a
passive poller.  A rank that dies leaves its peers wedged inside the
next collective, so on any non-zero exit the pod is torn down fail-fast
(SIGTERM → grace → SIGKILL, giving every survivor's preemption handler a
chance to checkpoint).  With ``--elastic``, the launcher then re-forms
the job from the surviving capacity — the new world is the largest
power-of-two divisor of the ORIGINAL (logical) world that the survivors
can fill — and relaunches with the elastic env contract
(``PADDLE_TPU_ELASTIC=1``, ``PADDLE_TPU_ELASTIC_LOGICAL_WORLD=<N>``,
``PADDLE_TPU_ELASTIC_RESTART=<n>``); workers resume from the last
committed checkpoint via ``Executor.restore_from_checkpoint``, whose
topology-shifted restore re-buckets state and schedule for the new
world.

Multi-host elastic (docs/elastic.md "Cross-host fleets"): with several
``--ips`` hosts, ``--elastic --fleet_dir <shared-fs dir>`` runs
`launch_collective_fleet` — each host's launcher joins the fleet
control plane (distributed/fleet_control.py), supervises its local
trainers AND its peers' membership, and on a lost host every surviving
launcher tears down, runs the two-phase survivor agreement (same
re-formed world, same restore step, picked from the run journals), and
relaunches with the ``PADDLE_TPU_FLEET_*`` contract; workers whose
writer world changed restore through the rank-merged
``CheckpointManager.load_merged``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from .launch_utils import (Cluster, Pod, get_cluster, start_local_trainers,
                           watch_local_trainers, poll_local_trainers,
                           terminate_procs, find_free_ports)

__all__ = ["launch_collective", "launch_collective_fleet", "launch_ps",
           "main", "elastic_world_size"]


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated host ips of the slice")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes per host (1 per TPU host)")
    p.add_argument("--started_port", type=int, default=None)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--run_mode", type=str, default="collective",
                   choices=["collective", "ps"])
    p.add_argument("--elastic", action="store_true",
                   help="supervise: on a lost rank, re-form the job from "
                        "survivors and relaunch resuming from the last "
                        "checkpoint (docs/elastic.md); with multiple "
                        "--ips hosts this needs --fleet_dir (the "
                        "cross-host rendezvous)")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="elastic relaunch budget before giving up")
    p.add_argument("--fleet_dir", type=str,
                   default=os.environ.get("PADDLE_TPU_FLEET_DIR"),
                   help="shared-filesystem rendezvous dir for multi-host "
                        "elastic (distributed/fleet_control.py): every "
                        "host's launcher joins membership here, agrees "
                        "on the survivor set after a lost host, and "
                        "exports the PADDLE_TPU_FLEET_* contract to its "
                        "workers")
    p.add_argument("--host_rank", type=int, default=None,
                   help="this host's index in --ips (default: the "
                        "position of POD_IP in --ips, else 0); must be "
                        "explicit when simulating several hosts on one "
                        "machine")
    p.add_argument("--host_capacity", type=int, default=None,
                   help="logical chips this host contributes to the "
                        "fleet world (default: --nproc_per_node); the "
                        "elastic logical world is the sum over --ips")
    p.add_argument("--member_timeout", type=float, default=20.0,
                   help="seconds without a membership refresh before a "
                        "fleet host counts as lost")
    p.add_argument("--journal_dir", type=str,
                   default=os.environ.get("PADDLE_TPU_JOURNAL_DIR"),
                   help="run-journal dir (exported to workers); the "
                        "fleet re-form reads the survivors' journals to "
                        "agree on the newest mutually-visible "
                        "checkpoint step")
    p.add_argument("--term_grace", type=float, default=10.0,
                   help="seconds between SIGTERM and SIGKILL at teardown")
    p.add_argument("--heartbeat_dir", type=str, default=None,
                   help="arm progress-based supervision: workers write "
                        "per-rank heartbeat files here each train step "
                        "(PADDLE_TPU_HEARTBEAT_DIR is exported to them); "
                        "a live rank whose heartbeat goes stale past "
                        "--stall_timeout is torn down like a dead one "
                        "(wedged-in-a-dead-collective detection)")
    p.add_argument("--stall_timeout", type=float, default=300.0,
                   help="seconds without a heartbeat before a rank "
                        "counts as stalled (must out-wait the longest "
                        "legitimate step, first-step compile included)")
    p.add_argument("--server_num", type=int, default=None)
    p.add_argument("--worker_num", type=int, default=None)
    p.add_argument("--servers", type=str, default="")
    p.add_argument("--workers", type=str, default="")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def elastic_world_size(survivors: int, logical_world: int) -> int:
    """Largest power-of-two divisor of `logical_world` that `survivors`
    ranks can fill — the world the re-formed mesh runs at (the elastic
    schedule requires the physical world to divide the logical one)."""
    if survivors < 1:
        return 0
    w = 1
    while w * 2 <= survivors and logical_world % (w * 2) == 0:
        w *= 2
    return w


def _spawn_pod(args, nproc, envs):
    node_ips = [ip.strip() for ip in args.ips.split(",") if ip.strip()]
    this_ip = os.environ.get("POD_IP", node_ips[0])
    if args.started_port is not None:
        ports = list(range(args.started_port, args.started_port + nproc))
    else:
        ports = find_free_ports(nproc)
    endpoints = [[f"{ip}:{port}" for port in ports] for ip in node_ips]
    devices_per_proc = [[i] for i in range(nproc)]
    cluster, pod = get_cluster(node_ips, this_ip, endpoints,
                               devices_per_proc)
    procs = start_local_trainers(cluster, pod, args.training_script,
                                 args.training_script_args,
                                 log_dir=args.log_dir, envs=envs)
    return cluster, procs


def launch_collective(args):
    """launch.py:198 parity, upgraded to a supervision loop.

    Non-elastic: any rank dying tears the pod down (fail-fast) and exits
    non-zero — survivors blocked in a dead collective must not hang the
    job forever.  ``--elastic``: the teardown is followed by re-forming
    the mesh from surviving capacity and relaunching with the elastic
    env contract; workers resume from the last committed checkpoint."""
    nproc = args.nproc_per_node
    n_ips = len([ip for ip in args.ips.split(",") if ip.strip()])
    if args.elastic and (n_ips > 1 or args.fleet_dir):
        # multi-host elastic: every host's launcher joins the shared-fs
        # rendezvous and the fleet controller drives the cross-host
        # survivor agreement (distributed/fleet_control.py)
        if not args.fleet_dir:
            sys.stderr.write(
                "--elastic with multiple --ips hosts needs --fleet_dir "
                "(a shared-filesystem rendezvous dir every host "
                "mounts; docs/elastic.md)\n")
            return 2
        return launch_collective_fleet(args)
    logical_world = nproc * n_ips
    hb_dir = args.heartbeat_dir
    restarts = 0
    while True:
        envs = {}
        if args.elastic:
            envs = {"PADDLE_TPU_ELASTIC": "1",
                    "PADDLE_TPU_ELASTIC_LOGICAL_WORLD": str(logical_world),
                    "PADDLE_TPU_ELASTIC_RESTART": str(restarts)}
        if hb_dir:
            # progress-based supervision (docs/observability.md): the
            # workers beat per train step; stale heartbeats from a
            # previous incarnation must not trip the NEW pod before its
            # first step, so the dir is swept at every (re)spawn
            envs["PADDLE_TPU_HEARTBEAT_DIR"] = hb_dir
            os.makedirs(hb_dir, exist_ok=True)
            for name in os.listdir(hb_dir):
                if name.startswith("heartbeat.rank"):
                    try:
                        os.unlink(os.path.join(hb_dir, name))
                    except OSError:
                        pass
        cluster, procs = _spawn_pod(args, nproc, envs)
        failed, stalled = [], []
        try:
            while True:
                procs, _done, failed = poll_local_trainers(procs)
                if failed or not procs:
                    break
                if hb_dir:
                    from ..observability.heartbeat import stalled_ranks
                    stalled = stalled_ranks(
                        hb_dir, args.stall_timeout,
                        ranks=[tp.rank for tp in procs])
                    if stalled:
                        break
                time.sleep(0.5)
        except KeyboardInterrupt:
            terminate_procs(procs, sigterm_grace=args.term_grace)
            return 1
        if not failed and not stalled:
            return 0
        if failed:
            codes = {tp.rank: tp.proc.poll() for tp in failed}
        else:
            # a wedged rank never exits on its own: a stale heartbeat IS
            # the failure signal, and the teardown below is what turns
            # "hangs forever" into "re-forms and finishes"
            codes = {r: "stalled" for r in stalled}
            sys.stderr.write(
                f"trainer rank(s) {stalled} stalled: no heartbeat for "
                f"{args.stall_timeout}s — treating as lost\n")
        # fail fast: peers of a dead rank are wedged in the next
        # collective — tear the pod down (SIGTERM lets their preemption
        # handlers checkpoint) instead of letting them hang
        terminate_procs(procs + failed, sigterm_grace=args.term_grace)
        survivors = nproc - len(failed) - len(stalled)
        if survivors < 1 and stalled and not failed:
            # stall-only teardown: every process was ALIVE and the host
            # answered — the capacity exists even though progress froze
            # (on a real mesh one wedged collective stalls every peer's
            # heartbeat at once).  Re-form minimally instead of declaring
            # the fleet gone; --max_restarts still bounds the loop.
            survivors = 1
        if not args.elastic or restarts >= args.max_restarts:
            sys.stderr.write(
                f"trainer rank(s) {sorted(codes)} exited non-zero "
                f"{codes}; pod terminated (elastic="
                f"{bool(args.elastic)}, restarts={restarts})\n")
            return 1
        new_world = elastic_world_size(survivors, logical_world)
        if new_world < 1:
            sys.stderr.write("no surviving capacity to re-form the mesh\n")
            return 1
        sys.stderr.write(
            f"elastic: rank(s) {sorted(codes)} lost ({codes}); re-forming "
            f"mesh {nproc} -> {new_world} of logical {logical_world}, "
            f"restart {restarts + 1}/{args.max_restarts}\n")
        nproc = new_world
        restarts += 1


def _spawn_fleet_pod(args, nproc, envs, member_hosts, my_host, node_ips):
    """Spawn THIS host's trainers for the current fleet formation.

    Trainer ranks are dense over the formation: sorted member hosts ×
    nproc (the CheckpointManager/journal/heartbeat rank layout every
    consumer of the formation shares).  Pods are selected by host INDEX,
    not by addr — simulated fleets run several 'hosts' on one ip."""
    from .launch_utils import Cluster, Pod, Trainer
    members = sorted(int(h) for h in member_hosts)
    my_index = members.index(int(my_host))
    if args.started_port is not None:
        ports = list(range(args.started_port, args.started_port + nproc))
    else:
        ports = find_free_ports(nproc)
    cluster = Cluster()
    rank = 0
    for idx, h in enumerate(members):
        ip = node_ips[h] if h < len(node_ips) else "127.0.0.1"
        pod = Pod(idx, ip)
        for i in range(nproc):
            # remote hosts' endpoints are decorative here (no connect in
            # the simulated fleet; a real slice passes --started_port so
            # every host derives the same port map)
            pod.trainers.append(Trainer(f"{ip}:{ports[i]}", rank, [i]))
            rank += 1
        cluster.pods.append(pod)
    pod = cluster.pods[my_index]
    procs = start_local_trainers(cluster, pod, args.training_script,
                                 args.training_script_args,
                                 log_dir=args.log_dir, envs=envs)
    ranks = [t.rank for t in pod.trainers]
    return procs, ranks


def launch_collective_fleet(args):
    """Multi-host elastic supervision: the per-host launcher joined to
    the fleet control plane (distributed/fleet_control.py).

    Each host's launcher (1) rendezvouses at --fleet_dir and agrees the
    epoch-0 formation, (2) spawns its local trainers with the elastic +
    fleet env contract, (3) supervises — local exit codes, heartbeat
    stalls, AND peer membership — and (4) on any loss tears its pod
    down and runs the two-phase survivor agreement so every surviving
    launcher re-forms to the SAME world and restore step, then
    relaunches.  Workers resume via the rank-merged restore
    (CheckpointManager.load_merged) when the writer world changed."""
    from .fleet_control import (FleetAgreementTimeout, FleetController,
                                fleet_rank)
    nproc = args.nproc_per_node
    node_ips = [ip.strip() for ip in args.ips.split(",") if ip.strip()]
    n_ips = max(1, len(node_ips))
    host = args.host_rank
    if host is None:
        pod_ip = os.environ.get("POD_IP", "")
        host = node_ips.index(pod_ip) if pod_ip in node_ips else 0
    capacity = args.host_capacity or nproc
    logical_world = capacity * n_ips
    hb_dir = args.heartbeat_dir
    ctl = FleetController(
        args.fleet_dir, host=host, capacity=capacity,
        logical_world=logical_world,
        member_timeout_s=args.member_timeout,
        journal_dir=args.journal_dir, heartbeat_dir=hb_dir,
        stall_timeout_s=(args.stall_timeout if hb_dir else None))
    # a reused fleet dir must not replay a previous run's agreement
    # (stale commits/proposals/barriers/done-members) into this one
    ctl.reset_rendezvous()
    try:
        commit = ctl.form(expect=range(n_ips))
    except FleetAgreementTimeout as e:
        sys.stderr.write(f"fleet formation failed: {e}\n")
        return 1
    restarts = 0
    while True:
        my_rank0 = fleet_rank(host, commit.members) * nproc
        ranks = list(range(my_rank0, my_rank0 + nproc))
        envs = {"PADDLE_TPU_ELASTIC": "1",
                "PADDLE_TPU_ELASTIC_LOGICAL_WORLD": str(logical_world),
                "PADDLE_TPU_ELASTIC_RESTART": str(restarts)}
        envs.update(ctl.env_for_workers(commit))
        if args.journal_dir:
            envs["PADDLE_TPU_JOURNAL_DIR"] = args.journal_dir
        if hb_dir:
            envs["PADDLE_TPU_HEARTBEAT_DIR"] = hb_dir
            os.makedirs(hb_dir, exist_ok=True)
            for name in os.listdir(hb_dir):  # sweep stale incarnations
                if name.startswith("heartbeat.rank"):
                    try:
                        os.unlink(os.path.join(hb_dir, name))
                    except OSError:
                        pass
        sys.stderr.write(
            f"fleet host {host}: epoch {commit.epoch} members "
            f"{commit.members} world {commit.world} restore_step "
            f"{commit.restore_step} — spawning ranks {ranks}\n")
        procs, ranks = _spawn_fleet_pod(args, nproc, envs,
                                        commit.members, host, node_ips)
        failed, stalled, lost = [], [], []
        try:
            while True:
                ctl.tick(ranks=ranks)
                procs, _done, failed = poll_local_trainers(procs)
                if failed:
                    break
                if not procs:  # every local trainer finished cleanly
                    ctl.leave()
                    ctl.close()
                    return 0
                if hb_dir:
                    from ..observability.heartbeat import stalled_ranks
                    stalled = stalled_ranks(
                        hb_dir, args.stall_timeout,
                        ranks=[tp.rank for tp in procs])
                    if stalled:
                        break
                lost = ctl.lost_members(commit)
                if lost:
                    break
                if ctl.reform_requested():
                    break
                time.sleep(0.3)
        except KeyboardInterrupt:
            terminate_procs(procs, sigterm_grace=args.term_grace)
            ctl.close()
            return 1
        why = (f"rank(s) failed {[tp.rank for tp in failed]}" if failed
               else f"rank(s) stalled {stalled}" if stalled
               else f"host(s) lost {lost}" if lost
               else "peer requested re-form")
        sys.stderr.write(
            f"fleet host {host}: {why} at epoch {commit.epoch} — "
            "tearing down local pod for survivor agreement\n")
        # SIGTERM first: survivors' preemption handlers stage their
        # final checkpoint before the fleet re-forms on top of it
        terminate_procs(procs + failed, sigterm_grace=args.term_grace)
        if restarts >= args.max_restarts:
            sys.stderr.write(
                f"fleet host {host}: restart budget exhausted "
                f"({restarts}/{args.max_restarts})\n")
            ctl.close()
            return 1
        try:
            commit = ctl.reform(commit)
        except FleetAgreementTimeout as e:
            sys.stderr.write(f"fleet re-form failed: {e}\n")
            ctl.close()
            return 1
        if commit.world < 1 or host not in commit.members:
            sys.stderr.write(
                f"fleet host {host}: not part of the re-formed fleet "
                f"{commit.members}\n")
            ctl.close()
            return 1
        restarts += 1


def launch_ps(args):
    """launch.py:248 parity — spawn pserver + trainer processes with the
    PADDLE_PORT / PADDLE_PSERVERS_IP_PORT_LIST / TRAINING_ROLE contract."""
    server_eps = [e for e in args.servers.split(",") if e]
    worker_eps = [e for e in args.workers.split(",") if e]
    if not server_eps:
        n = args.server_num or 1
        server_eps = [f"127.0.0.1:{p}" for p in find_free_ports(n)]
    if not worker_eps:
        n = args.worker_num or 1
        worker_eps = [f"127.0.0.1:{p}" for p in find_free_ports(n)]

    import subprocess
    procs = []
    base_env = dict(os.environ)
    base_env["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(server_eps)
    base_env["PADDLE_TRAINERS_NUM"] = str(len(worker_eps))
    for i, ep in enumerate(server_eps):
        env = dict(base_env, TRAINING_ROLE="PSERVER",
                   PADDLE_PORT=ep.split(":")[1], POD_IP=ep.split(":")[0],
                   PADDLE_TRAINER_ID=str(i))
        procs.append(subprocess.Popen(
            [sys.executable, "-u", args.training_script]
            + args.training_script_args, env=env))
    for i, ep in enumerate(worker_eps):
        env = dict(base_env, TRAINING_ROLE="TRAINER",
                   PADDLE_TRAINER_ID=str(i),
                   PADDLE_CURRENT_ENDPOINT=ep)
        procs.append(subprocess.Popen(
            [sys.executable, "-u", args.training_script]
            + args.training_script_args, env=env))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def main(argv=None):
    args = _parse_args(argv)
    if args.run_mode == "ps" or args.server_num or args.servers:
        return launch_ps(args)
    return launch_collective(args)


if __name__ == "__main__":
    sys.exit(main())
