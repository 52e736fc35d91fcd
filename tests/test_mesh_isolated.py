"""Tier-1 face of the mesh dispatcher (ISSUE 9).

Two layers, same pattern as test_overlap_isolated.py:

- jax-free, crypto-free unit tests of the lane packer (ops/mesh.py:
  pack_jobs / MeshPlan / pad_block / build_superblock / env knobs) run
  IN PROCESS — pure numpy bookkeeping, no kernel compiles;
- the kernel-level parity suite (tests/test_mesh.py) and the
  `tools/prep_bench.py --mesh` pack/demux/slot-leak/single-owner gate
  run in SUBPROCESSES with TM_TPU_PUREPY_CRYPTO=1, which must never
  leak into the main pytest process.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from tendermint_tpu.ops import mesh as ms
except ModuleNotFoundError:
    # The ops package __init__ wires the crypto.batch seam, which needs
    # the cryptography wheel this container lacks. mesh.py's packing
    # half is numpy + entry_block bookkeeping — load the module file
    # directly so the plan/pack unit tests still run in the main tier-1
    # process (mesh.py carries its own standalone entry_block loader).
    import importlib.util

    _p = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tendermint_tpu", "ops", "mesh.py",
    )
    _spec = importlib.util.spec_from_file_location(
        "_tm_tpu_mesh_standalone", _p
    )
    ms = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(ms)


class _J:
    def __init__(self, blk):
        self.entries = blk


def _blk(n, key=None, tag=0):
    eb = ms.EntryBlock(
        np.zeros((n, 32), dtype=np.uint8),
        np.zeros((n, 64), dtype=np.uint8),
        b"m" * n,
        np.arange(n + 1, dtype=np.int64),
    )
    eb.epoch_key = key
    if key is not None:
        eb.val_idx = np.arange(n, dtype=np.int32)
    return eb


class _Ep:
    """Epoch-entry stub: just the fields pad_block consumes."""

    def __init__(self, vp=64, key=b"ep"):
        self.vp = vp
        self.key = key


class TestPackJobs:
    def test_first_fit_same_key_shares_a_lane(self):
        plan, held = ms.pack_jobs(
            [_J(_blk(40, b"k")), _J(_blk(50, b"k")), _J(_blk(30))], 4, 128
        )
        assert not held
        assert [(l.key, l.n) for l in plan.lanes] == [(b"k", 90), (None, 30)]

    def test_mixed_keys_never_share_a_lane(self):
        plan, _ = ms.pack_jobs(
            [_J(_blk(10, b"a")), _J(_blk(10, b"b")), _J(_blk(10))], 4, 128
        )
        assert [l.key for l in plan.lanes] == [b"a", b"b", None]

    def test_overflow_jobs_are_held(self):
        jobs = [_J(_blk(128)) for _ in range(3)]
        plan, held = ms.pack_jobs(jobs, 2, 128)
        assert len(held) == 1 and held[0] is jobs[2]
        assert plan.n_lanes == 2 and plan.live == 256

    def test_job_over_lane_cap_raises(self):
        with pytest.raises(ValueError):
            ms.pack_jobs([_J(_blk(200))], 2, 128)

    def test_empty_job_gets_zero_width_span(self):
        plan, held = ms.pack_jobs([_J(_blk(0))], 2, 128)
        assert not held
        _, spans = ms.build_superblock(plan)
        assert len(spans) == 1 and spans[0][2] == 0

    def test_lane_count_rounds_to_pow2(self):
        plan, _ = ms.pack_jobs(
            [_J(_blk(128, bytes([i]))) for i in range(3)], 8, 128
        )
        assert len(plan.lanes) == 3 and plan.n_lanes == 4
        assert plan.pad == 128  # one pure padding lane

    def test_non_pow2_max_lanes_floors_to_pow2(self):
        # TM_TPU_MESH=3 must not mint 3-lane compiled shapes: the lane
        # budget floors to 2 and the third epoch's job is held
        plan, held = ms.pack_jobs(
            [_J(_blk(100, bytes([i]))) for i in range(3)], 3, 128
        )
        assert plan.n_lanes == 2 and len(plan.lanes) == 2
        assert len(held) == 1

    def test_empty_job_does_not_pin_or_demote_a_lane(self):
        # an empty (keyless) submission must not open a None-keyed lane
        # that demotes a same-warm-epoch pack to the uncached prep
        plan, held = ms.pack_jobs(
            [_J(_blk(0)), _J(_blk(40, b"k")), _J(_blk(30, b"k"))], 2, 128
        )
        assert not held
        assert [l.key for l in plan.lanes] == [b"k"]
        assert plan.epoch_key() == b"k"
        assert len(plan.empty_jobs) == 1
        _, spans = ms.build_superblock(plan)
        assert sum(1 for s in spans if s[2] == 0) == 1

    def test_occupancy_and_pad_are_complementary(self):
        plan, _ = ms.pack_jobs([_J(_blk(96)), _J(_blk(32))], 2, 128)
        assert plan.occupancy() + plan.pad_ratio() == pytest.approx(1.0)
        assert plan.live == 128 and plan.bucket == plan.n_lanes * 128


class TestSuperblock:
    def test_spans_tile_live_rows_exactly(self):
        plan, _ = ms.pack_jobs(
            [_J(_blk(96)), _J(_blk(31)), _J(_blk(5, b"z"))], 4, 128
        )
        block, spans = ms.build_superblock(plan)
        assert len(block) == plan.bucket
        rows = np.zeros(plan.bucket, dtype=bool)
        for _, off, n in spans:
            assert not rows[off:off + n].any()
            rows[off:off + n] = True
        assert int(rows.sum()) == plan.live
        # every span stays inside its lane (no straddling)
        lb = plan.lane_bucket
        for _, off, n in spans:
            assert off // lb == (off + max(n, 1) - 1) // lb

    def test_pad_rows_are_identity(self):
        p = ms.pad_block(5)
        assert (p.pub[:, 0] == 1).all() and (p.pub[:, 1:] == 0).all()
        assert (p.sig[:, 0] == 1).all() and (p.sig[:, 1:] == 0).all()
        assert p.msg_nbytes() == 0 and p.epoch_key is None

    def test_pad_rows_carry_epoch_identity_index(self):
        p = ms.pad_block(4, _Ep(vp=64, key=b"warm"))
        assert p.epoch_key == b"warm"
        assert (p.val_idx == 63).all()

    def test_lane_bucket_quantizes_to_ladder(self):
        plan, _ = ms.pack_jobs([_J(_blk(129))], 1, 10240)
        assert plan.lane_bucket == 1024
        plan2, _ = ms.pack_jobs([_J(_blk(17))], 1, 10240)
        assert plan2.lane_bucket == 128


class TestKnobs:
    def test_lanes_from_env(self, monkeypatch):
        monkeypatch.delenv("TM_TPU_MESH", raising=False)
        assert ms.lanes_from_env() == 0
        monkeypatch.setenv("TM_TPU_MESH", "0")
        assert ms.lanes_from_env() == 0
        monkeypatch.setenv("TM_TPU_MESH", "4")
        assert ms.lanes_from_env() == 4
        monkeypatch.setenv("TM_TPU_MESH", "garbage")
        assert ms.lanes_from_env() == 0

    def test_lane_cap_env(self, monkeypatch):
        monkeypatch.delenv("TM_TPU_MESH_LANE_BUCKET", raising=False)
        assert ms.lane_cap() == 10240
        monkeypatch.setenv("TM_TPU_MESH_LANE_BUCKET", "1024")
        assert ms.lane_cap() == 1024
        monkeypatch.setenv("TM_TPU_MESH_LANE_BUCKET", "4")
        # floored at the secp lane-bucket floor (ISSUE 19), not 128: the
        # scheme lane's per-row kernel cost makes small lanes worthwhile
        assert ms.lane_cap() == 16
        monkeypatch.setenv("TM_TPU_MESH_LANE_BUCKET", "999999")
        assert ms.lane_cap() == 10240  # clamped into the bucket ladder


def _purepy_env():
    from tendermint_tpu.libs import jaxcache

    env = dict(os.environ, TM_TPU_PUREPY_CRYPTO="1", JAX_PLATFORMS="cpu")
    env.pop("TM_TPU_DONATE", None)
    env.pop("TM_TPU_MESH", None)
    jaxcache.set_env(env)
    return env


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.time_limit(450)  # re-runs test_mesh.py: 131 s on a cold cache
def test_mesh_under_purepy_fallback():
    try:
        import cryptography  # noqa: F401

        pytest.skip("cryptography present; test_mesh runs directly")
    except ModuleNotFoundError:
        pass
    here = os.path.dirname(os.path.abspath(__file__))
    # devcheck armed for the whole run (ISSUE 8 pattern): the mesh
    # superbatch path must hold the device single-owner + canary
    # invariants under the runtime checkers, not just the AST pass
    env = dict(_purepy_env(), TM_TPU_DEVCHECK="1")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            os.path.join(here, "test_mesh.py"),
            "-q", "-m", "not slow", "-p", "no:cacheprovider",
        ],
        capture_output=True,
        env=env,
        cwd=_repo_root(),
        timeout=420,
    )
    tail = (r.stdout or b"").decode(errors="replace")[-3000:]
    assert r.returncode == 0, f"isolated test_mesh run failed:\n{tail}"


def test_prep_bench_mesh_gate():
    """ISSUE 9 satellite: the --mesh pack/demux-parity + slot-leak +
    single-owner gate on the mocked 2-lane mesh, wired into tier-1
    through the isolated runner (same pattern as --overlap)."""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(_repo_root(), "tools", "prep_bench.py"),
            "--mesh",
        ],
        capture_output=True,
        env=_purepy_env(),
        cwd=_repo_root(),
        timeout=180,
    )
    out = (r.stdout or b"").decode(errors="replace")
    err = (r.stderr or b"").decode(errors="replace")
    assert r.returncode == 0, f"--mesh gate failed:\n{out}\n{err[-2000:]}"
