"""Launcher unit tests (reference: test/single/test_run.py — arg
parsing and command-line construction asserted as strings, no SSH)."""

import os
import subprocess
import sys

import pytest

from horovod_tpu.runner.hosts import assign_ranks, parse_hosts
from horovod_tpu.runner.launch import _ssh_command, build_env, make_parser
from horovod_tpu.runner.hosts import RankInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHosts:
    def test_default_localhost(self):
        hs = parse_hosts(None, 4)
        assert len(hs) == 1 and hs[0].host == "localhost" \
            and hs[0].slots == 4

    def test_parse(self):
        hs = parse_hosts("h1:2, h2:3", 5)
        assert [(h.host, h.slots) for h in hs] == [("h1", 2), ("h2", 3)]

    def test_too_few_slots(self):
        with pytest.raises(ValueError, match="slots"):
            parse_hosts("h1:2", 4)

    def test_bad_slots(self):
        with pytest.raises(ValueError):
            parse_hosts("h1:x", 1)
        with pytest.raises(ValueError):
            parse_hosts("h1:0", 1)

    def test_assign_ranks(self):
        infos = assign_ranks(parse_hosts("h1:2,h2:2", 4), 4)
        assert [(i.rank, i.host, i.local_rank, i.cross_rank)
                for i in infos] == [
            (0, "h1", 0, 0), (1, "h1", 1, 0),
            (2, "h2", 0, 1), (3, "h2", 1, 1)]
        assert all(i.local_size == 2 and i.cross_size == 2
                   for i in infos)

    def test_assign_partial_last_host(self):
        infos = assign_ranks(parse_hosts("h1:2,h2:2", 3), 3)
        assert [i.host for i in infos] == ["h1", "h1", "h2"]
        assert infos[2].local_size == 1


class TestEnvAndSsh:
    def test_build_env(self):
        info = RankInfo(1, 4, 1, 2, 0, 2, "h1")
        env = build_env(info, "c:123", {"PATH": "/bin"})
        assert env["HOROVOD_RANK"] == "1"
        assert env["HOROVOD_SIZE"] == "4"
        assert env["HOROVOD_LOCAL_RANK"] == "1"
        assert env["HOROVOD_COORDINATOR_ADDR"] == "c:123"
        assert env["PATH"] == "/bin"

    def test_ssh_command_string(self):
        cmd = _ssh_command("hostB", ["python", "train.py"], 2222)
        assert cmd[0] == "ssh"
        assert "-p" in cmd and "2222" in cmd
        assert cmd[-2] == "hostB"
        remote = cmd[-1]
        # NOTHING env-shaped in the argv: the whole environment rides
        # the stdin pipe (read __HVD_ENV, base64-decode, eval).
        assert "read -r __HVD_ENV" in remote
        assert "base64 -d" in remote
        assert remote.endswith("python train.py")

    def test_env_stdin_payload(self):
        """The stdin env payload carries the full launcher env (minus
        host-specific shell state) plus the secret; nothing of it is
        in the argv (reference contrast: gloo_run inlines the env into
        the remote command — here /proc never sees it)."""
        import base64
        import io
        from horovod_tpu.runner import secret as S
        from horovod_tpu.runner.launch import _write_env_stdin

        class FakeProc:
            def __init__(self):
                self.stdin = io.BytesIO()
                self.stdin.close = lambda: None  # keep readable
        p = FakeProc()
        env = {"HOROVOD_RANK": "2", "MY_DATASET": "/data/x",
               "SSH_AUTH_SOCK": "/tmp/agent", "PWD": "/somewhere",
               "TERMINATION_GRACE": "30", "not an ident": "x"}
        _write_env_stdin(p, env, secret="deadbeef")
        script = base64.b64decode(p.stdin.getvalue()).decode()
        assert "export HOROVOD_RANK=2" in script
        assert "export MY_DATASET=/data/x" in script
        assert f"export {S.ENV_VAR}=deadbeef" in script
        # exact-name blocking must not eat prefixed user vars
        assert "export TERMINATION_GRACE=30" in script
        assert "SSH_AUTH_SOCK" not in script
        assert "PWD=" not in script
        assert "not an ident" not in script

    def test_parser(self):
        args = make_parser().parse_args(
            ["-np", "4", "-H", "h1:4", "python", "t.py"])
        assert args.num_proc == 4 and args.hosts == "h1:4"
        assert args.command == ["python", "t.py"]

    def test_tuning_flags_forward_as_env(self):
        """Reference: horovodrun's tuning flags mirror HOROVOD_* env
        vars and are forwarded to every worker."""
        from horovod_tpu.runner.launch import env_from_flags
        args = make_parser().parse_args([
            "-np", "2",
            "--fusion-threshold-bytes", "1048576",
            "--cycle-time-ms", "2.5",
            "--cache-capacity", "0",
            "--hierarchical-allreduce",
            "--timeline-filename", "/tmp/tl.json",
            "--timeline-mark-cycles",
            "--autotune", "--autotune-log-file", "/tmp/at.csv",
            "--no-stall-check",
            "--stall-shutdown-time-seconds", "120",
            "--log-level", "debug", "--log-hide-timestamp",
            "--controller", "python",
            "python", "t.py"])
        env = env_from_flags(args, base={})
        assert env == {
            "HOROVOD_FUSION_THRESHOLD": "1048576",
            "HOROVOD_CYCLE_TIME": "2.5",
            "HOROVOD_CACHE_CAPACITY": "0",
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_TIMELINE": "/tmp/tl.json",
            "HOROVOD_TIMELINE_MARK_CYCLES": "1",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_LOG": "/tmp/at.csv",
            "HOROVOD_STALL_CHECK_DISABLE": "1",
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "120.0",
            "HOROVOD_LOG_LEVEL": "debug",
            "HOROVOD_LOG_TIMESTAMP": "0",
            "HOROVOD_CONTROLLER": "python",
        }

    def test_unset_tuning_flags_leave_env_alone(self):
        from horovod_tpu.runner.launch import env_from_flags
        args = make_parser().parse_args(["-np", "2", "python", "t.py"])
        assert env_from_flags(args, base={"KEEP": "1"}) == {"KEEP": "1"}

    def test_every_tuning_flag_maps_to_declared_knob(self):
        """Each flag's target env var must exist in the config
        registry — no flag may write a knob nothing reads."""
        from horovod_tpu.common.config import KNOBS
        from horovod_tpu.runner.launch import _FLAG_ENV_MAP
        declared = {k.env for k in KNOBS}
        for _, var, _ in _FLAG_ENV_MAP:
            assert var in declared, var


def run_launcher(np_, script, extra_env=None, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # children don't need 8 fake devices
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         sys.executable, script],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.integration
class TestRealLaunch:
    @pytest.mark.parametrize("np_", [2, 4])
    def test_two_process_collectives(self, np_, multiproc_data_plane):
        # np=4 additionally exercises a live 2-member SUBSET process
        # set (inline dispatch path) alongside the world controller.
        r = run_launcher(np_, os.path.join("tests", "mp_worker.py"))
        assert r.returncode == 0, r.stdout + r.stderr
        assert r.stdout.count("ALL OK") == np_

    def test_failing_rank_propagates(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import os, sys\n"
            "sys.exit(3 if os.environ['HOROVOD_RANK'] == '1' else 0)\n")
        r = run_launcher(2, str(bad))
        assert r.returncode == 3
        assert "exited with code 3" in r.stdout + r.stderr


class TestDoctor:
    def test_check_build(self):
        from horovod_tpu.runner.doctor import check_build
        out = check_build()
        assert "XLA collectives" in out
        assert "[ ] NCCL" in out
        assert "JAX" in out


class TestSecretAuth:
    """HMAC-authenticated launcher services (reference:
    horovod/runner/common/util/secret.py + BasicService auth)."""

    def test_sign_verify_roundtrip(self):
        from horovod_tpu.runner import secret as S
        k = S.make_secret()
        sig = S.sign(k, b"/rank/h/0")
        assert S.verify(k, b"/rank/h/0", sig)
        assert not S.verify(k, b"/rank/h/1", sig)
        assert not S.verify(k, b"/rank/h/0", "")
        assert not S.verify(k, b"/rank/h/0", "deadbeef")

    def test_rendezvous_rejects_unsigned(self):
        import json
        import urllib.request
        import urllib.error
        from horovod_tpu.runner import secret as S
        from horovod_tpu.runner.elastic.rendezvous import \
            RendezvousServer
        k = S.make_secret()
        srv = RendezvousServer(secret=k)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            # unsigned GET -> 403
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/world", timeout=5)
            assert ei.value.code == 403
            # unsigned PUT (the write path) -> 403 and no state change
            body = json.dumps({"port": 31337}).encode()
            req = urllib.request.Request(
                f"{base}/notify/evil/0", data=body, method="PUT")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=5)
            assert ei.value.code == 403
            assert srv.notify_ports() == {}
            # correctly signed requests succeed
            path = "/notify/h/0"
            req = urllib.request.Request(
                f"{base}{path}", data=body, method="PUT",
                headers={S.HEADER: S.sign(k, path.encode() + body)})
            with urllib.request.urlopen(req, timeout=5) as resp:
                assert resp.status == 200
            assert srv.notify_ports() == {("h", 0): 31337}
            req = urllib.request.Request(
                f"{base}/world",
                headers={S.HEADER: S.sign(k, b"/world")})
            with urllib.request.urlopen(req, timeout=5) as resp:
                assert resp.status == 200
        finally:
            srv.stop()

    def test_notification_listener_rejects_unsigned(self, monkeypatch):
        import json
        import socket as socket_mod
        from horovod_tpu.runner import secret as S
        from horovod_tpu.elastic import notifications
        from horovod_tpu.elastic.worker import NotificationListener
        k = S.make_secret()
        monkeypatch.setenv(S.ENV_VAR, k)
        seen = []
        monkeypatch.setattr(notifications, "notify",
                            lambda info: seen.append(info))
        from horovod_tpu.runner.service import recv_frame, send_frame
        lst = NotificationListener()
        try:
            def poke(obj, key):
                with socket_mod.create_connection(
                        ("127.0.0.1", lst.port), timeout=5) as s:
                    send_frame(s, key, obj)
                    return recv_frame(s, k)  # replies signed with k
            # missigned poke (wrong key): rejected, no notification
            assert poke({"type": "hosts_updated", "epoch": 9},
                        "wrong-key") == {"error": "denied"}
            assert seen == []
            # signed poke: accepted
            assert poke({"type": "hosts_updated", "epoch": 3},
                        k) == {"ok": True}
            assert seen == [{"epoch": 3}]
        finally:
            lst.stop()

    def test_launcher_forwards_secret(self):
        """Every rank of a static launch gets the same HOROVOD_SECRET."""
        import subprocess
        import sys
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        code = ("import os; print('SECRET', "
                "os.environ.get('HOROVOD_SECRET', '')[:8])")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        lines = sorted(ln.split("]", 1)[1] for ln in
                       r.stdout.splitlines() if "SECRET" in ln)
        assert len(lines) == 2
        assert lines[0] == lines[1]
        assert len(lines[0].split()[-1]) == 8


def _ssh_localhost_available() -> bool:
    import subprocess
    try:
        r = subprocess.run(
            ["ssh", "-o", "BatchMode=yes", "-o",
             "StrictHostKeyChecking=no", "-o", "ConnectTimeout=3",
             "localhost", "true"], capture_output=True, timeout=10)
        return r.returncode == 0
    except (OSError, subprocess.TimeoutExpired):
        return False


@pytest.mark.integration
class TestSshLaunch:
    def test_ssh_to_localhost_rank(self):
        """Exercise the remote-ssh spawn path end-to-end by naming the
        host by hostname (not in LOCALHOSTS, so the launcher takes the
        ssh branch) — reference: gloo_run's exec_command over
        util/remote.py."""
        import socket as socket_mod
        import subprocess
        import sys
        if not _ssh_localhost_available():
            pytest.skip("no passwordless ssh to localhost")
        host = socket_mod.gethostname()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        code = ("import os; print('RANK', os.environ['HOROVOD_RANK'], "
                "'HOST', os.uname().nodename)")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "-H", f"localhost:1,{host}:1",
             sys.executable, "-c", code],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RANK 0" in r.stdout and "RANK 1" in r.stdout


def _write_fake_ssh(tmp_path):
    """An `ssh` stand-in that execs the remote command locally: parses
    away ssh options, drops the host, and runs the command string
    through sh with stdin passed through — so the launcher's REAL
    remote branch (option assembly, env exports, secret-on-stdin,
    output pumping) is exercised without sshd. Each invocation's argv
    is logged so tests can assert what crossed the 'wire'."""
    shim = tmp_path / "ssh"
    log = tmp_path / "ssh_argv.log"
    shim.write_text(f"""#!/bin/sh
printf '%s\\n' "$@" >> {log}
while [ $# -gt 0 ]; do
  case "$1" in
    -o|-p) shift 2 ;;
    -*) shift ;;
    *) break ;;
  esac
done
# $1 is the host; the rest is the remote command
shift
exec sh -c "$*"
""")
    shim.chmod(0o755)
    return shim, log


@pytest.mark.integration
class TestFakeSshLaunch:
    """Remote-spawn paths driven through a local ssh shim (the image
    has no ssh client; the shim keeps the launcher code path
    identical up to the exec)."""

    def _env(self, tmp_path):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PATH"] = str(tmp_path) + os.pathsep + env["PATH"]
        return env

    def test_static_launch_remote_branch(self, tmp_path):
        import subprocess
        import sys
        _, log = _write_fake_ssh(tmp_path)
        code = ("import os; print('RANK', os.environ['HOROVOD_RANK'], "
                "'SECRET_SET', bool(os.environ.get('HOROVOD_SECRET')))")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "-H", "localhost:1,fakehost:1",
             sys.executable, "-c", code],
            cwd=REPO, env=self._env(tmp_path), capture_output=True,
            text=True, timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RANK 0" in r.stdout and "RANK 1" in r.stdout
        # the worker HAS the secret (delivered over stdin)...
        assert "SECRET_SET True" in r.stdout
        # ...and NO env at all crossed the ssh argv
        argv = log.read_text()
        assert "HOROVOD_SECRET=" not in argv
        assert "HOROVOD_RANK=" not in argv
        assert "read -r __HVD_ENV" in argv

    def test_driver_launch_remote_task_service(self, tmp_path):
        """Probed launch with the task service for 'fakehost' started
        through the ssh shim: registration, NIC probe, election, and
        the run RPC all execute for real."""
        import subprocess
        import sys
        _, log = _write_fake_ssh(tmp_path)
        code = ("import os; print('RANK', os.environ['HOROVOD_RANK'], "
                "'IFACE', os.environ.get('HOROVOD_IFACE', '-'))")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             "-H", "localhost:1,fakehost:1", "--driver",
             "--start-timeout", "90",
             sys.executable, "-c", code],
            cwd=REPO, env=self._env(tmp_path), capture_output=True,
            text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RANK 0" in r.stdout and "RANK 1" in r.stdout
        argv = log.read_text()
        assert "task_service" in argv
        assert "HOROVOD_SECRET=" not in argv
