"""Launcher + multi-host control plane tests (reference tracker/dmlc_local.py
thread-per-process launch, keepalive restart on exit code 254, and the
scheduler barrier/allreduce protocol — SURVEY.md §2.4, §4)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from adapm_tpu import launcher
from adapm_tpu.parallel import control


def test_control_single_process_fallbacks():
    """All control primitives degrade to local no-ops in one process."""
    control.barrier("t")
    assert control.allreduce(3.0, "sum").tolist() == [3.0]
    assert control.allreduce([1.0, 2.0], "mean").tolist() == [1.0, 2.0]
    assert control.broadcast(np.arange(3)).tolist() == [0, 1, 2]
    assert control.num_processes() == 1
    assert control.process_id() == 0


def test_launch_local_env_contract(tmp_path):
    """launch_local spawns N ranks with the ADAPM_* env contract."""
    out = tmp_path / "ranks"
    out.mkdir()
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent(f"""
        import os
        rank = os.environ["ADAPM_PROCESS_ID"]
        n = os.environ["ADAPM_NUM_PROCESSES"]
        coord = os.environ["ADAPM_COORDINATOR"]
        open(r"{out}" + "/" + rank, "w").write(n + " " + coord)
    """))
    code = launcher.launch_local(3, [sys.executable, str(script)])
    assert code == 0
    files = sorted(os.listdir(out))
    assert files == ["0", "1", "2"]
    contents = {(out / f).read_text() for f in files}
    assert len(contents) == 1  # same num + coordinator for all ranks


def test_launch_local_keepalive(tmp_path):
    """Exit code 254 triggers a restart (reference dmlc_local.py:15-25)."""
    marker = tmp_path / "ran_once"
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        m = r"{marker}"
        if not os.path.exists(m):
            open(m, "w").write("x")
            sys.exit(254)
        sys.exit(0)
    """))
    code = launcher.launch_local(1, [sys.executable, str(script)])
    assert code == 0 and marker.exists()


def test_launch_local_propagates_failure(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text("import sys; sys.exit(7)")
    assert launcher.launch_local(
        2, [sys.executable, str(script)], keepalive=False) == 7


def test_launch_local_restart_budget_stops_crash_loop(tmp_path):
    """ISSUE 10 satellite: a rank that ALWAYS exits 254 used to be
    restarted forever at a fixed 0.5 s cadence (the reference
    dmlc_local.py contract). The hardened keepalive applies capped
    exponential backoff and gives up after the restart budget,
    propagating the 254 as the job's failure code."""
    import time as _time
    attempts = tmp_path / "attempts"
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent(f"""
        import sys
        with open(r"{attempts}", "a") as f:
            f.write("x")
        sys.exit(254)
    """))
    t0 = _time.monotonic()
    code = launcher.launch_local(
        1, [sys.executable, str(script)], keepalive=True,
        max_restarts=3, backoff_base_s=0.01, backoff_max_s=0.04)
    elapsed = _time.monotonic() - t0
    # budget exhausted: the crash loop stops and the 254 surfaces
    assert code == launcher.KEEPALIVE_EXIT_CODE
    # initial run + exactly max_restarts restarts, never unbounded
    assert attempts.read_text() == "x" * 4
    # backoff actually waited: 0.01 + 0.02 + 0.04 (capped) >= 0.07 s
    assert elapsed >= 0.07


def test_launch_local_keepalive_still_recovers_within_budget(tmp_path):
    """A transiently-crashing rank (254 once, then clean) still
    recovers under the hardened keepalive — the budget bounds crash
    LOOPS, not legitimate restarts."""
    marker = tmp_path / "ran_once"
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        m = r"{marker}"
        if not os.path.exists(m):
            open(m, "w").write("x")
            sys.exit(254)
        sys.exit(0)
    """))
    code = launcher.launch_local(
        1, [sys.executable, str(script)], keepalive=True,
        max_restarts=3, backoff_base_s=0.01)
    assert code == 0 and marker.exists()


def _rank_recorder(tmp_path):
    """A program that records its ADAPM_* env, used to verify the env
    contract each launch mode assembles."""
    out = tmp_path / "ranks"
    out.mkdir(exist_ok=True)
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent(f"""
        import os
        rank = os.environ["ADAPM_PROCESS_ID"]
        n = os.environ["ADAPM_NUM_PROCESSES"]
        coord = os.environ["ADAPM_COORDINATOR"]
        open(r"{out}" + "/" + rank, "w").write(n + " " + coord)
    """))
    return out, script


def test_launch_ssh_with_path_shim(tmp_path, monkeypatch):
    """ssh mode (reference tracker/dmlc_ssh.py): a PATH-shim `ssh` records
    argv and runs the remote command locally, verifying per-host command +
    env assembly without sshd."""
    out, script = _rank_recorder(tmp_path)
    bin_dir = tmp_path / "bin"
    bin_dir.mkdir()
    log = tmp_path / "ssh.log"
    shim = bin_dir / "ssh"
    # the remote command is the last argv; preceding args are opts + host
    shim.write_text(textwrap.dedent(f"""\
        #!/bin/sh
        printf '%s\\n' "$*" >> {log}
        for last; do :; done
        exec sh -c "$last"
    """))
    shim.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
    hosts = ["nodeA", "nodeB", "nodeC"]
    code = launcher.launch_ssh(hosts, [sys.executable, str(script)],
                               coordinator_port=23456)
    assert code == 0
    files = sorted(os.listdir(out))
    assert files == ["0", "1", "2"]
    contents = {(out / f).read_text() for f in files}
    # all ranks agree; coordinator is host 0 at the pinned port
    assert contents == {"3 nodeA:23456"}
    lines = log.read_text().splitlines()
    assert len(lines) == 3
    # the ssh processes run concurrently, so log lines may interleave in
    # any order — match each host's line by content, not position
    for rank, host in enumerate(hosts):
        ln = next(l for l in lines if f" {host} " in l)
        assert "StrictHostKeyChecking=no" in ln
        assert f"ADAPM_PROCESS_ID={rank}" in ln
        assert f"cd {os.getcwd()}" in ln


def test_launch_mpi_with_path_shim(tmp_path, monkeypatch):
    """mpi mode (reference tracker/dmlc_mpi.py): a PATH-shim `mpirun`
    records argv and spawns -n local copies with OMPI_COMM_WORLD_RANK set,
    verifying the MPI-env -> ADAPM-env bootstrap translation."""
    out, script = _rank_recorder(tmp_path)
    bin_dir = tmp_path / "bin"
    bin_dir.mkdir()
    log = tmp_path / "mpirun.log"
    shim = bin_dir / "mpirun"
    shim.write_text(textwrap.dedent(f"""\
        #!{sys.executable}
        import os, subprocess, sys
        args = sys.argv[1:]
        open(r"{log}", "a").write(" ".join(args) + chr(10))
        n, cmd, i = 1, [], 0
        while i < len(args):
            if args[i] == "-n":
                n = int(args[i + 1]); i += 2
            else:
                cmd.append(args[i]); i += 1
        procs = []
        for r in range(n):
            env = dict(os.environ)
            env["OMPI_COMM_WORLD_RANK"] = str(r)
            procs.append(subprocess.Popen(cmd, env=env))
        code = 0
        for p in procs:
            p.wait(); code = code or p.returncode
        sys.exit(code)
    """))
    shim.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
    code = launcher.launch_mpi(2, [sys.executable, str(script)],
                               coordinator_port=24567)
    assert code == 0
    files = sorted(os.listdir(out))
    assert files == ["0", "1"]
    contents = {(out / f).read_text() for f in files}
    assert len(contents) == 1  # same num + coordinator on every rank
    assert next(iter(contents)).startswith("2 ")
    assert ":24567" in next(iter(contents))
    assert "-n 2" in log.read_text()


def test_launcher_main_dispatches_all_modes(tmp_path, monkeypatch):
    """`python -m adapm_tpu.launcher --mode {local,ssh,mpi}` reaches the
    right launch function with parsed hostfile/port/keepalive flags."""
    calls = {}
    monkeypatch.setattr(
        launcher, "launch_local",
        lambda n, cmd, keepalive=True, **kw: calls.setdefault(
            "local", (n, cmd, keepalive)) and 0 or 0)
    monkeypatch.setattr(
        launcher, "launch_ssh",
        lambda hosts, cmd, coordinator_port=0: calls.setdefault(
            "ssh", (hosts, cmd, coordinator_port)) and 0 or 0)
    monkeypatch.setattr(
        launcher, "launch_mpi",
        lambda n, cmd, coordinator_port=0: calls.setdefault(
            "mpi", (n, cmd, coordinator_port)) and 0 or 0)
    hostfile = tmp_path / "hosts"
    hostfile.write_text("a\nb\n")
    launcher.main(["-n", "4", "--no-keepalive", "--", "prog", "--x"])
    launcher.main(["--mode", "ssh", "--hostfile", str(hostfile),
                   "--coordinator-port", "2222", "--", "prog"])
    launcher.main(["--mode", "mpi", "-n", "3",
                   "--coordinator-port", "3333", "--", "prog"])
    assert calls["local"] == (4, ["prog", "--x"], False)
    assert calls["ssh"] == (["a", "b"], ["prog"], 2222)
    assert calls["mpi"] == (3, ["prog"], 3333)


@pytest.mark.slow
def test_two_process_distributed_allreduce(tmp_path):
    """Real 2-process rendezvous through the jax.distributed coordinator
    (the scheduler's replacement): each rank contributes rank+1; the
    allreduce sum must be 3 in both processes."""
    script = tmp_path / "prog.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("PYTHONPATH", None)
        import jax
        jax.config.update("jax_platforms", "cpu")
        from adapm_tpu.parallel import control
        assert control.init_from_env()
        rank = control.process_id()
        control.barrier("start")
        total = control.allreduce(float(rank + 1), "sum")
        assert total.tolist() == [3.0], total
        control.barrier("end")
        print("RANK", rank, "OK", flush=True)
    """))
    env = dict(os.environ)
    # child processes need the repo importable
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(launcher.__file__)))
    coordinator = f"localhost:{launcher.free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, str(script)],
        env=launcher.make_env(r, 2, coordinator, env),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{o}"
        assert f"RANK {r} OK" in o


def test_launch_local_refuses_several_ranks_on_a_tpu_host(monkeypatch):
    """ISSUE 21 satellite: local ranks get nothing that divides a host's
    chips, so on a TPU host more than one local rank is refused up
    front (before anything is spawned) — decided without touching a jax
    backend; a CPU-pinned environment is never a TPU host."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launcher.local_tpu_chips() == 0
    monkeypatch.setattr(launcher, "local_tpu_chips", lambda: 4)
    with pytest.raises(RuntimeError, match="one TPU host"):
        launcher.launch_local(2, [sys.executable, "-c", "raise SystemExit(3)"])
    # one rank per host is the supported shape and still launches
    assert launcher.launch_local(
        1, [sys.executable, "-c", "pass"], keepalive=False) == 0
