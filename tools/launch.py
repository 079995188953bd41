#!/usr/bin/env python
"""Cluster launcher.

TPU-native analogue of the reference's tools/launch.py (which delegates to
dmlc-core trackers: local/ssh/mpi/sge/yarn — tools/launch.py:33-60,
SURVEY §2.7). The reference starts scheduler + server + worker OS
processes; here every process is a worker and the "scheduler" is the
jax.distributed coordinator (SURVEY §5.8), so launching means: start N
copies of the training script with MXNET_TPU_{COORDINATOR,NUM_PROCS,
PROC_ID} set, then `mxnet_tpu.parallel.dist.init()` inside the script wires
them into one mesh.

Modes:
  --launcher local  spawn N local processes (the dmlc "local" tracker;
                    multi-process CPU emulation). NOT a way to share one
                    host's chips: a chip belongs to one process, and
                    nothing here gives each worker its own (no
                    TPU_VISIBLE_CHIPS or equivalent is set), so on a TPU
                    host the second worker fails or hangs at start-up.
                    Drive a host's chips from ONE process with a device
                    list (context=[mx.tpu(i) ...]).
  --launcher ssh    one process per host listed in --hostfile
                    (the dmlc "ssh" tracker)
  --launcher mpi    delegate process placement to mpirun; per-rank
                    identity comes from the MPI env (OMPI_COMM_WORLD_* /
                    PMI_*) which dist.init() reads once the launcher has
                    pinned the coordinator (the dmlc "mpi" tracker)
  --launcher sge    submit a qsub array job whose tasks derive their rank
                    from SGE_TASK_ID (the dmlc "sge" tracker)
  --launcher yarn   print the YARN distributed-shell submission with the
                    coordinator env wired (the dmlc "yarn" tracker; like
                    the tpu mode, cluster submission runs via the
                    cluster's own CLI)
  --launcher tpu    print the gcloud command that runs the script on every
                    worker of a TPU pod slice (pods launch via the cloud
                    CLI, not raw ssh)

--dry-run prints the exact command/script any launcher would run without
executing it.

Example:
  python tools/launch.py -n 4 --launcher local python train.py --epochs 1
"""
import argparse
import os
import shlex
import subprocess
import sys


def _coord(host="127.0.0.1"):
    """coordinator address `host:port` — the one place the default port
    and MXNET_TPU_PORT override live."""
    return "%s:%d" % (host, int(os.environ.get("MXNET_TPU_PORT", "12975")))


def _read_hostfile(path):
    with open(path) as f:
        return [h.strip().split()[0] for h in f if h.strip()]


def launch_local(n, cmd, env_extra=None, n_servers=0):
    """Local multi-process launch (dmlc local tracker analogue; CPU
    workers — see the module docstring on chips). With
    n_servers > 0, also spawns that many parameter-server processes and
    wires every process with the comma-separated MXNET_TPU_PS_URI list
    (the reference's `launch.py -n W -s S` worker/server topology; big
    arrays shard across the whole server group, kvstore_dist.h:276-314)."""
    import socket

    procs = []
    servers = []
    coord = _coord()
    ps_uri = None
    if n_servers > 0:
        ports = []
        for _ in range(n_servers):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            s.close()
        ps_uri = ",".join("127.0.0.1:%d" % p for p in ports)
        for sid in range(n_servers):
            env = dict(os.environ)
            env.update(env_extra or {})
            env["MXNET_TPU_ROLE"] = "server"
            env["MXNET_TPU_SERVER_ID"] = str(sid)
            env["MXNET_TPU_PS_URI"] = ps_uri
            env["MXNET_TPU_NUM_WORKERS"] = str(n)
            servers.append(subprocess.Popen(cmd, env=env))
    for rank in range(n):
        env = dict(os.environ)
        env.update(env_extra or {})
        env["MXNET_TPU_COORDINATOR"] = coord
        env["MXNET_TPU_NUM_PROCS"] = str(n)
        env["MXNET_TPU_PROC_ID"] = str(rank)
        if ps_uri:
            env["MXNET_TPU_ROLE"] = "worker"
            env["MXNET_TPU_WORKER_RANK"] = str(rank)
            env["MXNET_TPU_PS_URI"] = ps_uri
            env["MXNET_TPU_NUM_WORKERS"] = str(n)
        procs.append(subprocess.Popen(cmd, env=env))
    rc = 0
    # Port pre-allocation above is bind-then-close, so another process can
    # steal a port before the server binds it (TOCTOU). Rather than letting
    # the group hang on 60s connect retries, fail fast: a server exiting
    # while workers still run means it never came up.
    import time

    running = list(procs)
    while running:
        for p in list(running):
            if p.poll() is not None:
                running.remove(p)
                rc = rc or p.returncode
        for s in (servers if running else ()):
            # rc 0 is a clean stop_server() exit (stragglers may still be
            # finishing); nonzero while workers run means the server never
            # came up (e.g. lost its pre-allocated port to a bind race).
            # Skipped once all workers are reaped: a server dying during
            # shutdown must not fail a successful job.
            if s.poll() is not None and s.returncode != 0:
                sys.stderr.write(
                    "launch.py: server process exited early (rc=%s) while "
                    "workers are running — likely lost its pre-allocated "
                    "port; killing the group\n" % s.returncode)
                for p in running + [x for x in servers if x.poll() is None]:
                    p.terminate()
                for p in running + servers:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        p.wait()
                # a worker failure already recorded in rc stays the verdict
                # (workers define success); the server rc is the fallback
                return rc or s.returncode or 1
        if running:
            time.sleep(0.2)
    # servers only exit on a kv.stop_server() RPC; whether or not the
    # workers sent one, shut the group down now. Server exit status does
    # NOT fold into the launcher rc — workers define success (the
    # reference tracker likewise tears servers down after workers).
    for p in servers:
        if p.poll() is None:
            p.terminate()
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    return rc


def launch_ssh(hosts, cmd, repo_dir):
    """One process per host over ssh (dmlc ssh tracker analogue)."""
    coord = _coord(hosts[0])
    procs = []
    for rank, host in enumerate(hosts):
        envs = ("MXNET_TPU_COORDINATOR=%s MXNET_TPU_NUM_PROCS=%d "
                "MXNET_TPU_PROC_ID=%d" % (coord, len(hosts), rank))
        remote = "cd %s && %s %s" % (shlex.quote(repo_dir), envs,
                                     " ".join(shlex.quote(c) for c in cmd))
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no", host,
                                       remote]))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def _mpi_env_flags(var, value):
    """mpirun flags exporting var=value to every rank, in the installed
    MPI's dialect: OpenMPI takes `-x VAR=val`, MPICH/hydra and Intel MPI
    take `-genv VAR val` (hydra aborts on an unknown `-x`). Flavor is
    sniffed from `mpirun --version`; unknown/absent mpirun defaults to
    the OpenMPI form."""
    flavor = ""
    try:
        out = subprocess.run(["mpirun", "--version"], capture_output=True,
                             text=True, timeout=10)
        flavor = (out.stdout or "") + (out.stderr or "")
    except (FileNotFoundError, subprocess.TimeoutExpired):
        pass
    if "HYDRA" in flavor or "Intel" in flavor or "MPICH" in flavor:
        return ["-genv", var, value]
    return ["-x", "%s=%s" % (var, value)]


def launch_mpi(n, cmd, hostfile=None, dry_run=False):
    """Delegate placement to mpirun (dmlc mpi tracker analogue,
    reference tools/launch.py:33-60). mpirun exports per-rank identity
    (OMPI_COMM_WORLD_RANK/SIZE or PMI_RANK/SIZE) which
    `mxnet_tpu.parallel.dist.init()` reads; the launcher's job is only
    to pin the coordinator address every rank should dial.

    Coordinator placement ASSUMES mpirun's default by-slot mapping puts
    rank 0 on the first hostfile entry. With custom mappings (--map-by
    node, rankfiles, relative slot counts) rank 0 can land elsewhere —
    set MXNET_TPU_COORD_HOST to the host that will run rank 0 and it is
    honored verbatim."""
    host = os.environ.get("MXNET_TPU_COORD_HOST") or "127.0.0.1"
    if hostfile and not os.environ.get("MXNET_TPU_COORD_HOST"):
        hosts = _read_hostfile(hostfile)
        if hosts:
            host = hosts[0]
    coord = _coord(host)
    mpi_cmd = ["mpirun", "-np", str(n)]
    if hostfile:
        mpi_cmd += ["--hostfile", hostfile]
    # NUM_PROCS rides along for scripts that read it directly (rank
    # itself comes from the MPI env: OMPI_COMM_WORLD_RANK / PMI_RANK)
    mpi_cmd += (_mpi_env_flags("MXNET_TPU_COORDINATOR", coord)
                + _mpi_env_flags("MXNET_TPU_NUM_PROCS", str(n)) + cmd)
    if dry_run:
        print(" ".join(shlex.quote(c) for c in mpi_cmd))
        return 0
    env = dict(os.environ, MXNET_TPU_COORDINATOR=coord)
    try:
        return subprocess.call(mpi_cmd, env=env)
    except FileNotFoundError:
        sys.stderr.write("launch.py: mpirun not found on PATH\n")
        return 127


def sge_job_script(n, cmd):
    """The qsub array-job script text: N tasks, rank = SGE_TASK_ID - 1
    (dist.init reads SGE_TASK_ID/FIRST/STEPSIZE/LAST).

    Coordinator placement: jax.distributed's coordinator service is
    HOSTED BY RANK 0 — SGE task 1 — which the scheduler places on an
    arbitrary exec host (the submit host would only be right by luck;
    the reference's dmlc sge tracker could pin the submit host because
    its rendezvous ran there as a separate process, which
    jax.distributed does not do). So task 1 publishes its own hostname
    to a shared-FS rendezvous file under -cwd (SGE jobs share the
    submit cwd) and the other tasks poll for it before exec'ing the
    command. MXNET_TPU_COORD_HOST overrides: set it to the exec host
    that will run task 1 and the file dance is skipped."""
    joined = " ".join(shlex.quote(c) for c in cmd)
    port = int(os.environ.get("MXNET_TPU_PORT", "12975"))
    lines = [
        "#!/bin/bash",
        "#$ -cwd",
        "#$ -t 1-%d" % n,
        "#$ -S /bin/bash",
    ]
    coord_host = os.environ.get("MXNET_TPU_COORD_HOST")
    if coord_host:
        # resolved NOW, at generation time: a shell $(hostname) would
        # expand per-task on each execution host and every rank would
        # dial a different address
        lines.append("export MXNET_TPU_COORDINATOR=%s" % _coord(coord_host))
    else:
        lines += [
            'RDV=".mxnet_tpu_coord.$JOB_ID"',
            'if [ "$SGE_TASK_ID" = "1" ]; then',
            # write-then-rename so pollers never read a partial file;
            # task 1 owns the file's lifetime (trap removes it on exit —
            # without it every job litters the shared cwd, and a
            # qsub -r y rerun of task 1 on a NEW host could hand peers
            # the dead previous host). The rerun case also rewrites
            # unconditionally, so late-joining peers see the new host.
            '  trap \'rm -f "$RDV"\' EXIT',
            '  hostname -f > "$RDV.tmp" && mv "$RDV.tmp" "$RDV"',
            "fi",
            "for _i in $(seq 600); do",
            '  [ -f "$RDV" ] && break',
            "  sleep 1",
            "done",
            'if [ ! -f "$RDV" ]; then',
            '  echo "launch.py[sge]: rendezvous file $RDV never appeared'
            ' (is -cwd on a shared filesystem?)" >&2',
            "  exit 1",
            "fi",
            'export MXNET_TPU_COORDINATOR="$(cat "$RDV"):%d"' % port,
        ]
    lines += [joined, ""]
    return "\n".join(lines)


def launch_sge(n, cmd, dry_run=False):
    """Submit the array job via qsub (dmlc sge tracker analogue)."""
    script = sge_job_script(n, cmd)
    if dry_run:
        print(script)
        return 0
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".sh", delete=False) as f:
        f.write(script)
        path = f.name
    try:
        return subprocess.call(["qsub", "-sync", "y", path])
    except FileNotFoundError:
        sys.stderr.write("launch.py: qsub not found on PATH\n")
        return 127


def launch_yarn(n, cmd):
    """Print the YARN distributed-shell submission (dmlc yarn tracker
    analogue). Like the tpu mode, the cluster's own CLI performs the
    submission. Rank identity: the distributed-shell exports no task
    index, but every container's CONTAINER_ID ends in a dense 1-based
    ordinal where _000001 is the application master — worker rank =
    ordinal - 2."""
    coord = _coord(os.environ.get("MXNET_TPU_COORD_HOST")
                   or "$COORD_HOST")
    joined = " ".join(shlex.quote(c) for c in cmd)
    shell = ("export MXNET_TPU_PROC_ID=$(( 10#${CONTAINER_ID##*_} - 2 )); "
             + joined)
    print("# Submit via the YARN distributed-shell application:")
    print("yarn jar $HADOOP_HOME/share/hadoop/yarn/"
          "hadoop-yarn-applications-distributedshell-*.jar "
          "-jar $HADOOP_HOME/share/hadoop/yarn/"
          "hadoop-yarn-applications-distributedshell-*.jar "
          "-num_containers %d "
          "-shell_env MXNET_TPU_COORDINATOR=%s "
          "-shell_env MXNET_TPU_NUM_PROCS=%d "
          "-shell_command %s"
          % (n, coord, n, shlex.quote(shell)))
    return 0


def launch_tpu_pod(args, cmd):
    """Print the pod-slice launch command; TPU pods are driven by the cloud
    CLI (every worker runs the same script; jax initializes from pod
    metadata, no MXNET_TPU_* env needed)."""
    joined = " ".join(shlex.quote(c) for c in cmd)
    print("# Run on every worker of the pod slice:")
    print("gcloud compute tpus tpu-vm ssh %s --worker=all "
          "--command=%s" % (args.tpu_name or "$TPU_NAME",
                            shlex.quote("cd %s && %s"
                                        % (os.getcwd(), joined))))
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, default=1)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="parameter-server processes (local launcher)")
    ap.add_argument("--launcher",
                    choices=["local", "ssh", "mpi", "sge", "yarn", "tpu"],
                    default="local")
    ap.add_argument("--hostfile",
                    help="one host per line (ssh/mpi launchers)")
    ap.add_argument("--tpu-name", help="TPU pod name (tpu launcher)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print what would run without executing")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    cmd = args.command
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, cmd,
                              n_servers=args.num_servers))
    elif args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--hostfile required for ssh launcher")
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        sys.exit(launch_ssh(hosts[:args.num_workers] if args.num_workers > 1
                            else hosts, cmd, os.getcwd()))
    elif args.launcher == "mpi":
        sys.exit(launch_mpi(args.num_workers, cmd, hostfile=args.hostfile,
                            dry_run=args.dry_run))
    elif args.launcher == "sge":
        sys.exit(launch_sge(args.num_workers, cmd, dry_run=args.dry_run))
    elif args.launcher == "yarn":
        sys.exit(launch_yarn(args.num_workers, cmd))
    else:
        sys.exit(launch_tpu_pod(args, cmd))


if __name__ == "__main__":
    main()
