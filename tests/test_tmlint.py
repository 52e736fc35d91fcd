"""tmlint framework + rule tests (ISSUE 8).

Pure-AST layer: everything here runs without jax, numpy, or the crypto
wheel — fixture snippets per rule (positive / negative / suppressed /
baselined), suppression-comment parsing, baseline round-trip, the CLI
exit-code contract, and THE tier-1 gate: tmlint over the real tree must
report zero non-baselined findings.

The positive fixtures double as the static half of the seeded-regression
requirement: `PR7_ALIAS_BUG` re-introduces the exact readback-aliasing
shape PR 7 shipped and fixed, and `SINGLE_OWNER_BUG` a device launch
outside the dispatcher — each pass must flag its bug class.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.tmlint import core, run_source  # noqa: E402
from tools.tmlint.rules import ALL_RULES, RULES_BY_NAME  # noqa: E402

OPS_PATH = "tendermint_tpu/ops/fake_mod.py"
SIMNET_PATH = "tendermint_tpu/simnet/fake_mod.py"
REACTOR_PATH = "tendermint_tpu/blocksync/fake_mod.py"
LIGHT_PATH = "tendermint_tpu/light/fake_service.py"
HOT_PATH = "tendermint_tpu/ops/entry_block.py"


def lint(src: str, path: str, rule: str = None):
    rules = [RULES_BY_NAME[rule]] if rule else ALL_RULES
    return run_source(textwrap.dedent(src), path, rules)


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# the seeded-regression fixtures: each checker's bug class, re-introduced


PR7_ALIAS_BUG = """
    import numpy as np

    def _resolve(spans, dev):
        arr = np.asarray(dev)          # zero-copy view of the XLA buffer
        for job, off, n in spans:
            job.future.set_result(arr[off : off + n])
"""

PR7_ALIAS_FIXED = """
    import numpy as np

    def _resolve(spans, dev):
        arr = np.asarray(dev)
        if not arr.flags.owndata:
            arr = np.array(arr, copy=True)
        for job, off, n in spans:
            job.future.set_result(arr[off : off + n])
"""

SINGLE_OWNER_BUG = """
    import jax

    def sneaky_verify(args):
        return jax.device_put(args)    # device touch outside the dispatcher
"""


class TestSeededRegressions:
    def test_pr7_alias_bug_is_flagged(self):
        fs = lint(PR7_ALIAS_BUG, OPS_PATH, "donation-aliasing")
        assert fs, "the PR-7 readback-aliasing bug class must be flagged"
        assert "set_result" in fs[0].message.lower() or "escapes" in fs[0].message

    def test_pr7_fixed_shape_is_clean(self):
        assert not lint(PR7_ALIAS_FIXED, OPS_PATH, "donation-aliasing")

    def test_single_owner_violation_is_flagged(self):
        fs = lint(SINGLE_OWNER_BUG, REACTOR_PATH, "device-ownership")
        assert fs and fs[0].rule == "device-ownership"

    def test_single_owner_ok_inside_dispatcher(self):
        assert not lint(
            SINGLE_OWNER_BUG, "tendermint_tpu/ops/pipeline.py",
            "device-ownership",
        )


# ---------------------------------------------------------------------------
# per-rule positive / negative / suppressed / baselined


class TestDonationAliasing:
    def test_positive_return_asarray(self):
        src = """
            import numpy as np
            def f(dev):
                return np.asarray(dev)
        """
        assert rules_of(lint(src, OPS_PATH)) == ["donation-aliasing"]

    def test_positive_tainted_slice_append(self):
        src = """
            import numpy as np
            def f(devs):
                out = []
                for d in devs:
                    res = np.asarray(d)[:4]
                    out.append(res)
                return out
        """
        assert "donation-aliasing" in rules_of(lint(src, OPS_PATH))

    def test_positive_annotated_assignment(self):
        # review fix: a type annotation must not launder the taint
        src = """
            import numpy as np
            def f(dev):
                res: np.ndarray = np.asarray(dev)
                return res
        """
        assert rules_of(lint(src, OPS_PATH)) == ["donation-aliasing"]

    def test_positive_walrus_assignment(self):
        src = """
            import numpy as np
            def f(dev):
                if (res := np.asarray(dev)) is not None:
                    return res
        """
        assert rules_of(lint(src, OPS_PATH)) == ["donation-aliasing"]

    def test_positive_tuple_assignment(self):
        src = """
            import numpy as np
            def f(dev, other):
                a, b = np.asarray(dev), other
                return a
        """
        assert rules_of(lint(src, OPS_PATH)) == ["donation-aliasing"]

    def test_negative_owned_copy(self):
        src = """
            import numpy as np
            def f(dev):
                return np.asarray(dev)[:4].copy()
        """
        assert not lint(src, OPS_PATH, "donation-aliasing")

    def test_positive_owned_init_overwritten_by_view(self):
        # review fix: last binding per name wins — an owned init must not
        # launder a later device-view reassignment (the PR-7 shape)
        src = """
            import numpy as np
            def f(dev, n):
                out = np.zeros(n)
                out = np.asarray(dev)[:n]
                return out
        """
        assert rules_of(lint(src, OPS_PATH)) == ["donation-aliasing"]

    def test_negative_owndata_guard_pattern(self):
        src = """
            import numpy as np
            def f(dev):
                arr = np.asarray(dev)
                arr = np.array(arr, copy=True)
                return arr[:3]
        """
        assert not lint(src, OPS_PATH, "donation-aliasing")

    def test_negative_outside_ops(self):
        src = """
            import numpy as np
            def f(dev):
                return np.asarray(dev)
        """
        assert not lint(src, "tendermint_tpu/light/client.py",
                        "donation-aliasing")

    def test_negative_owned_array_of_launch(self):
        # the ISSUE 19 secp chunked-verify shape: np.array(...) copies
        # by default (numpy 2), so slicing/appending the result is clean
        src = """
            import numpy as np
            def f(kern, args, n):
                res = np.array(kern(*args))
                return res[:n]
        """
        assert not lint(src, OPS_PATH, "donation-aliasing")

    def test_suppressed(self):
        src = """
            import numpy as np
            def f(dev):
                return np.asarray(dev)  # tmlint: disable=donation-aliasing — consumer copies
        """
        assert not lint(src, OPS_PATH, "donation-aliasing")


class TestDeviceOwnership:
    def test_positive_entry_points(self):
        src = """
            def f(backend, args):
                k = backend.cached_kernel(None, True)
                return k(*args)
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["device-ownership"]

    def test_positive_qualified_transfer(self):
        src = """
            def f(_dpool, args):
                return _dpool.transfer(args)
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["device-ownership"]

    def test_negative_bare_transfer_is_not_flagged(self):
        src = """
            def f(conn, data):
                return conn.transfer(data)
        """
        assert not lint(src, REACTOR_PATH, "device-ownership")

    def test_negative_whitelisted_module(self):
        src = """
            import jax
            def f(x):
                return jax.device_put(x)
        """
        assert not lint(src, "tendermint_tpu/ops/device_pool.py",
                        "device-ownership")

    def test_suppressed_next_line_comment(self):
        src = """
            import jax
            def f(x):
                # tmlint: disable=device-ownership — sanctioned one-off
                return jax.device_put(x)
        """
        assert not lint(src, REACTOR_PATH, "device-ownership")

    def test_positive_mesh_launch_outside_whitelist(self):
        """ISSUE 9 satellite: a non-whitelisted mesh superbatch launch —
        building the mesh kernel or touching the replicated epoch
        tables outside the dispatcher modules — is flagged."""
        src = """
            from tendermint_tpu.ops import sharded

            def sneaky_mesh_verify(mesh, args):
                fn = sharded.mesh_valid_fn(mesh, donate=True)
                return fn(*args)
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["device-ownership"]
        src_tbl = """
            def sneaky_tables(ep, mesh):
                return ep.sharded_xla_tables(mesh)
        """
        assert rules_of(lint(src_tbl, REACTOR_PATH)) == ["device-ownership"]
        src_sh = """
            from tendermint_tpu.ops.sharded import epoch_tables_sharded

            def sneaky(ep, mesh):
                return epoch_tables_sharded(ep, mesh)
        """
        assert rules_of(lint(src_sh, REACTOR_PATH)) == ["device-ownership"]

    def test_negative_mesh_module_is_whitelisted(self):
        src = """
            def prep(block, plan, _sharded, mesh):
                fn = _sharded.mesh_valid_fn_cached(mesh, None)
                return fn
        """
        assert not lint(src, "tendermint_tpu/ops/mesh.py",
                        "device-ownership")
        # the packing entry point itself is an ENTRY_POINT elsewhere
        src_prep = """
            from tendermint_tpu.ops import mesh

            def f(block, plan):
                return mesh.prepare_superbatch(block, plan)
        """
        assert rules_of(lint(src_prep, REACTOR_PATH)) == ["device-ownership"]

    # -- ISSUE 11: the light service's dispatch path -----------------------

    def test_positive_light_service_direct_device(self):
        """A light-service-shaped module touching the device directly —
        launching, transferring, or wiring a mocked-device double
        into the pipeline — is flagged; the service must submit through
        AsyncBatchVerifier."""
        src = """
            import jax

            def verify_unique(self, stages):
                return [jax.device_put(st.entries) for st in stages]
        """
        assert rules_of(lint(src, LIGHT_PATH)) == ["device-ownership"]
        src_mock = """
            from tendermint_tpu.ops._testing import mock_light_prepare

            def install_fast_path(pl):
                pl.AsyncBatchVerifier._prepare = mock_light_prepare(
                    pl.AsyncBatchVerifier._prepare, 0.0
                )
        """
        assert rules_of(lint(src_mock, LIGHT_PATH)) == ["device-ownership"]

    def test_negative_light_service_submit_pattern(self):
        """The real service shape — EntryBlocks submitted to the shared
        verifier, verdicts via futures — is clean."""
        src = """
            def verify_unique(self, stages, fid):
                futs = [self._v.submit(st.entries, flow=fid) for st in stages]
                return [f.result(timeout=600) for f in futs]
        """
        assert not lint(src, LIGHT_PATH, "device-ownership")

    # -- ISSUE 20: BLS aggregation lane launch builders --------------------

    def test_positive_bls_pairing_launch_outside_whitelist(self):
        """ISSUE 20 satellite: jitting the fused multi-pairing kernel or
        driving the direct BLS code-row path outside the dispatcher
        whitelist is flagged — aggregated commits reach the device only
        through AsyncBatchVerifier / the mesh."""
        src = """
            from tendermint_tpu.ops import bls_verify

            def sneaky_pairing(gx, gy, masks, coeffs):
                fn = bls_verify.jitted_bls_verify(True)
                return fn(gx, gy, masks, coeffs)
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["device-ownership"]
        src_kern = """
            def sneaky_kernel(_backend, blk):
                return _backend.bls_kernel(blk.bucket)(blk.rows)
        """
        assert rules_of(lint(src_kern, REACTOR_PATH)) == ["device-ownership"]
        src_codes = """
            from tendermint_tpu.ops.backend import verify_batch_bls_codes

            def sneaky_codes(blk):
                return verify_batch_bls_codes(blk)
        """
        assert rules_of(lint(src_codes, REACTOR_PATH)) == ["device-ownership"]

    def test_negative_bls_kernel_module_is_whitelisted(self):
        """The kernel-definition module and the sanctioned direct path in
        ops/backend.py hold these call sites legitimately."""
        src = """
            def _warm(gx, gy, masks, coeffs):
                return jitted_bls_verify(False)(gx, gy, masks, coeffs)
        """
        assert not lint(src, "tendermint_tpu/ops/bls_verify.py",
                        "device-ownership")
        src_backend = """
            def verify_batch_bls(blk):
                codes = verify_batch_bls_codes(blk)
                return codes == 1
        """
        assert not lint(src_backend, "tendermint_tpu/ops/backend.py",
                        "device-ownership")


class TestFleetTransport:
    """ISSUE 18: the fleet wire codec has exactly three sanctioned homes
    (fleet/wire.py, fleet/client.py, fleet/server.py) — frame encode /
    parse call sites anywhere else fork a versioned protocol surface."""

    def test_positive_encode_outside_fleet(self):
        src = """
            from tendermint_tpu.fleet import wire

            def sneaky_send(sock, rid, block):
                for buf in wire.encode_submit(rid, block, lane="rogue"):
                    sock.sendall(buf)
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["fleet-transport"]

    def test_positive_parse_and_decoder_outside_fleet(self):
        src = """
            from tendermint_tpu.fleet.wire import FrameDecoder, parse_frame

            def sneaky_recv(sock):
                dec = FrameDecoder()
                for payload in dec.feed(sock.recv(65536)):
                    yield parse_frame(payload)
        """
        assert sorted(rules_of(lint(src, REACTOR_PATH))) == [
            "fleet-transport", "fleet-transport"
        ]

    def test_negative_raw_sockets_stay_legal(self):
        """Generic socket traffic is NOT the invariant — rpc/, privval/,
        and p2p/ own their sockets; only the fleet codec is fenced."""
        src = """
            def send_all(conn, data):
                conn.sendall(data)
                return conn.recv(4096)
        """
        assert not lint(src, "tendermint_tpu/p2p/fake_transport.py",
                        "fleet-transport")

    def test_negative_whitelisted_modules(self):
        src = """
            from . import wire

            def reply(outbox, rid, verdicts):
                outbox.put(wire.encode_verdicts(rid, verdicts))
        """
        for path in ("tendermint_tpu/fleet/wire.py",
                     "tendermint_tpu/fleet/client.py",
                     "tendermint_tpu/fleet/server.py"):
            assert not lint(src, path, "fleet-transport")

    def test_negative_fleet_client_usage_is_clean(self):
        """The sanctioned consumer shape — a lane handing windows to a
        FleetClient via the LaneSpec verifier seam — is clean."""
        src = """
            from tendermint_tpu.fleet.client import FleetClient

            def make_lane_verifier(addr):
                return FleetClient(addr, name="node-a")
        """
        assert not lint(src, REACTOR_PATH, "fleet-transport")

    def test_suppressed_next_line_comment(self):
        src = """
            from tendermint_tpu.fleet import wire

            def forge(rid):
                # tmlint: disable=fleet-transport — wire-format test rig
                return wire.encode_error(rid, 3, "boom")
        """
        assert not lint(src, REACTOR_PATH, "fleet-transport")


class TestSimnetDeterminism:
    def test_positive_wall_clock(self):
        src = """
            import time
            def f():
                return time.time()
        """
        assert rules_of(lint(src, SIMNET_PATH)) == ["simnet-determinism"]

    def test_positive_global_rng_and_entropy(self):
        src = """
            import os, random
            def f():
                return random.random() + len(os.urandom(8))
        """
        assert rules_of(lint(src, SIMNET_PATH)) == [
            "simnet-determinism", "simnet-determinism"
        ]

    def test_positive_unseeded_random_instance(self):
        src = """
            import random
            def f():
                return random.Random()
        """
        assert lint(src, SIMNET_PATH, "simnet-determinism")

    def test_negative_seeded_rng_and_injected_clock(self):
        src = """
            import random
            def f(self, seed):
                rng = random.Random(seed)
                return rng.random() + self._now()
        """
        assert not lint(src, SIMNET_PATH, "simnet-determinism")

    def test_positive_set_iteration(self):
        src = """
            def f(peers):
                live = set(peers)
                for p in live:
                    p.poke()
        """
        assert lint(src, SIMNET_PATH, "simnet-determinism")

    def test_negative_sorted_set_iteration(self):
        src = """
            def f(peers):
                for p in sorted(set(peers)):
                    p.poke()
        """
        assert not lint(src, SIMNET_PATH, "simnet-determinism")

    def test_negative_outside_scope(self):
        src = """
            import time
            def f():
                return time.time()
        """
        assert not lint(src, "tendermint_tpu/rpc/fake.py",
                        "simnet-determinism")

    def test_positive_light_scope(self):
        """ISSUE 11 satellite: light/ is in the deterministic scope — a
        wall-clock read in a light-client module is flagged (the
        sanctioned default lives in libs/timeutil, injected via now_fn)."""
        src = """
            import time as _time
            def _now_ts():
                return _time.time()
        """
        assert rules_of(
            lint(src, "tendermint_tpu/light/client.py")
        ) == ["simnet-determinism"]

    def test_negative_light_injected_clock(self):
        src = """
            def verify_at(self, height, now=None):
                now = now or self._now_ts()
                return (height, now)
        """
        assert not lint(src, "tendermint_tpu/light/client.py",
                        "simnet-determinism")

    def test_light_tree_is_clean_without_suppressions(self):
        """The REAL light/ modules lint clean with zero suppressions —
        the satellite's acceptance: clock injection landed everywhere."""
        import tokenize

        light_dir = os.path.join(REPO_ROOT, "tendermint_tpu", "light")
        for name in sorted(os.listdir(light_dir)):
            if not name.endswith(".py"):
                continue
            path = os.path.join(light_dir, name)
            with open(path) as fh:
                src = fh.read()
            rel = f"tendermint_tpu/light/{name}"
            assert not run_source(src, rel, [RULES_BY_NAME["simnet-determinism"]]), \
                f"{rel} has determinism findings"
            with open(path, "rb") as fh:
                for tok in tokenize.tokenize(fh.readline):
                    if tok.type == tokenize.COMMENT:
                        assert "disable=simnet-determinism" not in tok.string, \
                            f"{rel} suppresses the determinism pass"

    def test_suppressed(self):
        src = """
            import time
            def f():
                return time.time()  # tmlint: disable=simnet-determinism — wall budget only
        """
        assert not lint(src, SIMNET_PATH, "simnet-determinism")


class TestHotPathPurity:
    def test_positive_per_element_loop(self):
        src = """
            def f(xs, out):
                for i in range(len(xs)):
                    out.append(xs[i])
        """
        assert rules_of(lint(src, HOT_PATH)) == ["hot-path-purity"]

    def test_positive_entries_loop(self):
        src = """
            def f(entries):
                acc = []
                for e in entries:
                    acc.append(e[0])
                return acc
        """
        assert lint(src, HOT_PATH, "hot-path-purity")

    def test_negative_grouped_loop(self):
        src = """
            import numpy as np
            def f(lens, buf):
                groups = []
                for length in np.unique(lens):
                    groups.append((length, buf))
                return groups
        """
        assert not lint(src, HOT_PATH, "hot-path-purity")

    def test_negative_other_module(self):
        src = """
            def f(xs, out):
                for i in range(len(xs)):
                    out.append(xs[i])
        """
        assert not lint(src, "tendermint_tpu/ops/backend.py",
                        "hot-path-purity")

    def test_fallback_marker_covers_function(self):
        src = """
            def f(xs):  # tmlint: fallback — object-path composer
                out = []
                for i in range(len(xs)):
                    out.append(xs[i])
                return out
        """
        assert not lint(src, HOT_PATH, "hot-path-purity")


class TestLockDiscipline:
    def test_positive_bare_acquire(self):
        src = """
            def f(self):
                self._mtx.acquire()
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["lock-discipline"]

    def test_negative_semaphore_and_with(self):
        src = """
            def f(self):
                self._sem.acquire()
                with self._mtx:
                    pass
        """
        assert not lint(src, REACTOR_PATH, "lock-discipline")

    def test_negative_assigned_acquire_result(self):
        src = """
            def f(self):
                slot = self._pool.acquire(("k",))
                return slot
        """
        assert not lint(src, REACTOR_PATH, "lock-discipline")

    def test_positive_lambda_thread_target(self):
        src = """
            import threading
            def f():
                t = threading.Thread(target=lambda: None)
                t.start()
        """
        assert rules_of(lint(src, REACTOR_PATH)) == ["lock-discipline"]

    def test_positive_device_touching_thread_target(self):
        src = """
            import threading, jax
            def worker(x):
                jax.device_put(x)
            def f():
                threading.Thread(target=worker).start()
        """
        fs = lint(src, REACTOR_PATH)
        # the worker body also trips device-ownership; the thread-target
        # finding is the lock-discipline one
        assert "lock-discipline" in rules_of(fs)

    def test_suppressed(self):
        src = """
            def f(self):
                self._mtx.acquire()  # tmlint: disable=lock-discipline — paired API
        """
        assert not lint(src, REACTOR_PATH, "lock-discipline")

    # -- ISSUE 13: .result() under a state mutex ------------------------

    def test_positive_result_under_mutex(self):
        """The bad shape satellite 2 removed from the mempool: waiting on
        a device verdict while holding the mempool's state mutex — the
        completing thread (the ingress completer) needs that same lock to
        finish CheckTx, so this deadlocks."""
        src = """
            def check_tx(self, tx):
                fut = self._ingress.submit(tx)
                with self._mtx:
                    verdict = fut.result(timeout=300)
                return verdict
        """
        fs = lint(src, "tendermint_tpu/mempool/fake_mod.py",
                  "lock-discipline")
        assert fs and "_mtx" in fs[0].message

    def test_positive_result_under_module_level_mtx_name(self):
        src = """
            def f(mtx, fut):
                with mtx:
                    return fut.result()
        """
        assert rules_of(
            lint(src, REACTOR_PATH, "lock-discipline")
        ) == ["lock-discipline"]

    def test_negative_result_outside_mutex(self):
        """The fixed shape: resolve the future first, take the lock for
        the state mutation only."""
        src = """
            def check_tx(self, tx):
                fut = self._ingress.submit(tx)
                verdict = fut.result(timeout=300)
                with self._mtx:
                    self._insert(tx, verdict)
                return verdict
        """
        assert not lint(src, "tendermint_tpu/mempool/fake_mod.py",
                        "lock-discipline")

    def test_negative_result_under_coordination_lock(self):
        """Locks NOT named *mtx* are out of scope: pipeline.py's chunked
        submit collects sub-results under `done_lock` by design (the
        completer there never needs that lock)."""
        src = """
            def _combine(done_lock, futs):
                out = []
                with done_lock:
                    for f in futs:
                        out.append(f.result())
                return out
        """
        assert not lint(src, "tendermint_tpu/ops/fake_mod.py",
                        "lock-discipline")

    def test_negative_result_in_nested_def_under_mutex(self):
        """A callback DEFINED under the lock runs later on another frame
        — defining it is not waiting under the lock."""
        src = """
            def f(self, fut):
                with self._mtx:
                    def _done(f):
                        return f.result()
                    fut.add_done_callback(_done)
        """
        assert not lint(src, "tendermint_tpu/mempool/fake_mod.py",
                        "lock-discipline")

    # -- ISSUE 13: ingress accumulator device discipline ------------------

    def test_positive_ingress_wiring_mock_outside_whitelist(self):
        """Wiring the mempool mocked-device double into the pipeline from
        production ingress code is a device violation — only bench/gate
        harnesses (and ops/_testing.py itself) may do that."""
        src = """
            from tendermint_tpu.ops._testing import mock_mempool_prepare

            def fast_path(pl):
                pl.AsyncBatchVerifier._prepare = mock_mempool_prepare(
                    pl.AsyncBatchVerifier._prepare, 0.0
                )
        """
        assert rules_of(
            lint(src, "tendermint_tpu/mempool/ingress.py",
                 "device-ownership")
        ) == ["device-ownership"]

    def test_negative_ingress_accumulator_submit_path(self):
        """The real accumulator shape — EntryBlocks submitted to the
        shared verifier with an ingress priority, verdicts via futures —
        is clean: no device entry point in sight."""
        src = """
            def _flush_device(self, batch):
                block = self._pack(batch)
                fut = self._verifier.submit(
                    block, priority=1
                )
                fut.add_done_callback(
                    self._on_device_done
                )
        """
        assert not lint(src, "tendermint_tpu/mempool/ingress.py",
                        "device-ownership")

    # -- ISSUE 15: vote-ingress submit path ------------------------------

    def test_positive_vote_submit_under_window_mutex(self):
        """The shape _flush_window must never regress to: submitting the
        packed EntryBlock while still holding the accumulator's window
        mutex. submit() blocks on the pipeline depth semaphore under
        backpressure, and the verdict pump needs _mtx to stage the next
        window — a full stall of live-vote ingress."""
        src = """
            def _flush_window(self, key):
                with self._mtx:
                    batch = self._windows.pop(key)
                    fut = self._ensure_verifier().submit(
                        batch.block, priority=0
                    )
                return fut
        """
        fs = lint(src, "tendermint_tpu/consensus/fake_ingress.py",
                  "lock-discipline")
        assert fs and "depth semaphore" in fs[0].message

    def test_positive_vote_verdict_wait_under_mutex(self):
        """Waiting for a vote verdict under the VoteSet mutex is the
        ISSUE-13 shape resurfacing on the consensus side."""
        src = """
            def add_vote(self, vote):
                fut = self._ingress.submit(vote)
                with self._mtx:
                    return fut.result(timeout=60)
        """
        fs = lint(src, "tendermint_tpu/consensus/fake_ingress.py",
                  "lock-discipline")
        assert fs and "_mtx" in fs[0].message

    def test_negative_vote_ingress_stage_then_submit(self):
        """The real accumulator discipline: stage under _mtx, pop the
        window, RELEASE, then submit — clean."""
        src = """
            def _flush_window(self, key):
                with self._mtx:
                    batch = self._windows.pop(key)
                    self._inflight += 1
                fut = self._ensure_verifier().submit(
                    batch.block, priority=0
                )
                return fut
        """
        assert not lint(src, "tendermint_tpu/consensus/fake_ingress.py",
                        "lock-discipline")

    def test_negative_executor_pool_submit_under_lock(self):
        """Executor-pool submits are non-blocking enqueues, not pipeline
        dispatches — out of shape-4 scope even under a mutex."""
        src = """
            def f(self, entries):
                with self._mtx:
                    fut = prep_pool.submit(self._prepare, entries)
                return fut
        """
        assert not lint(src, "tendermint_tpu/ops/fake_mod.py",
                        "lock-discipline")

    def test_positive_vote_mock_wired_from_consensus(self):
        """mock_vote_prepare is a bench/gate double: wiring it from
        production consensus code is a device violation."""
        src = """
            from tendermint_tpu.ops._testing import mock_vote_prepare

            def fast_votes(pl):
                pl.AsyncBatchVerifier._prepare = mock_vote_prepare(
                    pl.AsyncBatchVerifier._prepare, 0.0
                )
        """
        assert rules_of(
            lint(src, "tendermint_tpu/consensus/vote_ingress.py",
                 "device-ownership")
        ) == ["device-ownership"]


class TestIngressDiscipline:
    """ISSUE 17: the four hand-rolled windowed accumulators were unified
    behind ops/ingress.py; a fifth private batching stack (flush-timer
    thread + EntryBlock assembly in one module) must never grow back."""

    ACCUMULATOR_BUG = """
        import threading
        from ..ops.entry_block import EntryBlock

        class MyAccumulator:
            def __init__(self, verifier):
                self._verifier = verifier
                self._pending = []
                self._thread = threading.Thread(
                    target=self._flush_loop, daemon=True)
                self._thread.start()

            def _flush_loop(self):
                while True:
                    block = EntryBlock.from_entries(
                        [(p.pub, p.msg, p.sig) for p in self._pending])
                    self._verifier.submit(block)
    """

    def test_positive_private_accumulator(self):
        """The exact pre-ISSUE-17 shape: a per-workload flusher thread
        assembling EntryBlocks for submission."""
        fs = lint(self.ACCUMULATOR_BUG, REACTOR_PATH, "ingress-discipline")
        assert rules_of(fs) == ["ingress-discipline"]
        assert "LaneSpec" in fs[0].message

    def test_positive_window_timer_thread(self):
        src = """
            import threading
            from .entry_block import EntryBlock

            def start(pending, verifier):
                def _window_timer():
                    verifier.submit(EntryBlock.from_entries(pending))
                threading.Thread(target=_window_timer).start()
        """
        assert rules_of(
            lint(src, OPS_PATH, "ingress-discipline")
        ) == ["ingress-discipline"]

    def test_negative_assembly_without_thread(self):
        """Building EntryBlocks alone is fine — the replay prep path and
        every bench do it; the engine owns the flush cadence."""
        src = """
            from ..ops.entry_block import EntryBlock

            def prepare(votes):
                return EntryBlock.from_entries(
                    [(v.pub, v.msg, v.sig) for v in votes])
        """
        assert not lint(src, REACTOR_PATH, "ingress-discipline")

    def test_negative_thread_without_assembly(self):
        """Threads with flush-ish targets but no EntryBlock assembly are
        out of scope (the soak harness drains queues on threads)."""
        src = """
            import threading

            def start(q):
                threading.Thread(target=q.drain_loop, daemon=True).start()
        """
        assert not lint(src, REACTOR_PATH, "ingress-discipline")

    def test_negative_unrelated_thread_target(self):
        """A worker thread that is not a flush loop does not pair with
        assembly elsewhere in the module."""
        src = """
            import threading
            from .entry_block import EntryBlock

            def start(sock, votes):
                threading.Thread(target=sock.read_loop).start()
                return EntryBlock.from_entries(votes)
        """
        assert not lint(src, OPS_PATH, "ingress-discipline")

    def test_whitelisted_engine_module(self):
        """The engine itself is the one sanctioned owner."""
        assert not lint(self.ACCUMULATOR_BUG,
                        "tendermint_tpu/ops/ingress.py",
                        "ingress-discipline")

    def test_suppressed(self):
        src = """
            import threading
            from .entry_block import EntryBlock

            def start(pending, verifier):
                def _flush():
                    verifier.submit(EntryBlock.from_entries(pending))
                # tmlint: disable=ingress-discipline -- migration shim
                threading.Thread(target=_flush).start()
        """
        assert not lint(src, OPS_PATH, "ingress-discipline")


# ---------------------------------------------------------------------------
# framework mechanics


class TestSuppressionParsing:
    def test_multi_rule_and_justification(self):
        sup = core.Suppressions.scan(
            "x = 1  # tmlint: disable=a,b — because reasons\n"
        )
        assert sup.by_line[1] == {"a", "b"}

    def test_comment_only_line_covers_next(self):
        sup = core.Suppressions.scan(
            "# tmlint: disable=r\nx = 1\n"
        )
        assert sup.suppressed("r", 1) and sup.suppressed("r", 2)

    def test_disable_file(self):
        sup = core.Suppressions.scan("# tmlint: disable-file=r\nx = 1\n")
        assert sup.suppressed("r", 99)

    def test_disable_all(self):
        sup = core.Suppressions.scan("x = 1  # tmlint: disable=all\n")
        assert sup.suppressed("anything", 1)

    def test_unrelated_comments_ignored(self):
        sup = core.Suppressions.scan("x = 1  # a normal comment\n")
        assert not sup.by_line and not sup.file_wide

    def test_def_line_suppression_spans_body(self):
        src = textwrap.dedent("""
            import numpy as np
            def f(dev):  # tmlint: disable=donation-aliasing — whole fn
                a = np.asarray(dev)
                return a
        """)
        assert not run_source(
            src, OPS_PATH, [RULES_BY_NAME["donation-aliasing"]]
        )


class TestBaseline:
    SRC = """
        import numpy as np
        def f(dev):
            return np.asarray(dev)
    """

    def _findings(self, pad=0):
        return lint("\n" * pad + textwrap.dedent(self.SRC), OPS_PATH,
                    "donation-aliasing")

    def test_fingerprints_survive_line_drift(self):
        a = core.fingerprint_findings(self._findings(pad=0))
        b = core.fingerprint_findings(self._findings(pad=7))
        assert a == b and len(a) == 1

    def test_round_trip_and_gate(self, tmp_path):
        fs = self._findings()
        path = str(tmp_path / "BASE.json")
        core.write_baseline(path, fs)
        base = core.load_baseline(path)
        new, old = core.apply_baseline(fs, base)
        assert not new and len(old) == 1
        # a NEW finding (different source text) is not covered
        fs2 = lint(
            """
            import numpy as np
            def g(dev):
                return np.asarray(dev)[:2]
            """,
            OPS_PATH, "donation-aliasing",
        )
        new2, _ = core.apply_baseline(fs2, base)
        assert len(new2) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert core.load_baseline(str(tmp_path / "nope.json")) == set()

    def test_duplicate_lines_disambiguate_by_occurrence(self):
        src = """
            import numpy as np
            def f(dev):
                return np.asarray(dev)
            def g(dev):
                return np.asarray(dev)
        """
        fps = core.fingerprint_findings(lint(src, OPS_PATH,
                                             "donation-aliasing"))
        assert len(fps) == 2 and fps[0] != fps[1]

    def test_parse_error_is_a_finding(self):
        fs = run_source("def broken(:\n", OPS_PATH, ALL_RULES)
        assert rules_of(fs) == ["parse-error"]


# ---------------------------------------------------------------------------
# THE gate + CLI contract


class TestTreeGate:
    def test_tree_has_zero_nonbaselined_findings(self):
        """Tier-1 gate: tmlint over the real tree, with the committed
        baseline, must be clean — a new finding fails the build."""
        findings = core.run_paths(["tendermint_tpu"], REPO_ROOT, ALL_RULES)
        baseline = core.load_baseline(
            os.path.join(REPO_ROOT, "LINT_BASELINE.json")
        )
        new, _ = core.apply_baseline(findings, baseline)
        assert not new, "new tmlint findings:\n" + "\n".join(
            f"  {f!r}" for f in new
        )

    def test_baseline_has_no_stale_entries(self):
        """The committed baseline only shrinks: every fingerprint in it
        must still correspond to a real finding (delete fixed ones)."""
        findings = core.run_paths(["tendermint_tpu"], REPO_ROOT, ALL_RULES)
        live = set(core.fingerprint_findings(findings))
        baseline = core.load_baseline(
            os.path.join(REPO_ROOT, "LINT_BASELINE.json")
        )
        assert baseline <= live, f"stale baseline entries: {baseline - live}"


class TestCLI:
    def _run(self, *args, cwd=REPO_ROOT):
        return subprocess.run(
            [sys.executable, "-m", "tools.tmlint", *args],
            capture_output=True, text=True, cwd=cwd, timeout=120,
        )

    def test_exit_0_on_clean_tree(self):
        r = self._run()
        assert r.returncode == 0, r.stdout + r.stderr

    def test_exit_1_on_finding_and_json_output(self, tmp_path):
        mod = tmp_path / "tendermint_tpu" / "ops"
        mod.mkdir(parents=True)
        (mod / "bad.py").write_text(textwrap.dedent(PR7_ALIAS_BUG))
        r = self._run("tendermint_tpu", "--root", str(tmp_path),
                      "--no-baseline", "--json")
        assert r.returncode == 1, r.stdout + r.stderr
        data = json.loads(r.stdout)
        assert not data["ok"] and data["new"]
        assert data["new"][0]["rule"] == "donation-aliasing"

    def test_exit_2_on_unknown_rule(self):
        r = self._run("--rules", "no-such-rule")
        assert r.returncode == 2

    def test_exit_2_on_missing_path(self):
        r = self._run("no/such/dir")
        assert r.returncode == 2

    def test_write_baseline_refuses_rule_or_path_subset(self):
        # review fix: a subset-scoped rewrite would drop every other
        # rule's grandfathered fingerprints
        r = self._run("--write-baseline", "--rules", "donation-aliasing")
        assert r.returncode == 2
        r = self._run("tendermint_tpu/ops", "--write-baseline")
        assert r.returncode == 2

    def test_write_baseline_then_clean(self, tmp_path):
        mod = tmp_path / "tendermint_tpu" / "ops"
        mod.mkdir(parents=True)
        (mod / "bad.py").write_text(textwrap.dedent(PR7_ALIAS_BUG))
        r1 = self._run("tendermint_tpu", "--root", str(tmp_path),
                       "--write-baseline")
        assert r1.returncode == 0, r1.stdout + r1.stderr
        assert (tmp_path / "LINT_BASELINE.json").exists()
        r2 = self._run("tendermint_tpu", "--root", str(tmp_path))
        assert r2.returncode == 0, r2.stdout + r2.stderr

    def test_list_rules_names_all_five(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        for name in ("donation-aliasing", "device-ownership",
                     "simnet-determinism", "hot-path-purity",
                     "lock-discipline"):
            assert name in r.stdout
