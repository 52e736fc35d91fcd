"""Light-client verification service (ISSUE 11): batched verdicts must
be byte-identical to the sequential light/verifier.py path — ok headers,
forged commits (blame string included), conflicting headers, expired
trust, the exactly-1/3 trust-level edge — while cross-request same-epoch
sig work coalesces through the shared device pipeline and verdicts
stream back in completion order. Plus the /light_verify RPC endpoint
(JSON + chunked NDJSON streaming) and the simnet e2e: hundreds of
simulated clients against a rotating-valset cluster with adversarial
clients, flight-recorder chains RPC-arrival → verdict.

Needs a working ed25519 signer: with the `cryptography` wheel the module
runs directly; without it, tests/test_light_service_isolated.py re-runs
it in a subprocess under TM_TPU_PUREPY_CRYPTO=1.
"""

import importlib.util
import json
import os
import sys
import urllib.request
from dataclasses import replace as dc_replace

import pytest

if importlib.util.find_spec("cryptography") is None and not os.environ.get(
    "TM_TPU_PUREPY_CRYPTO"
):
    pytest.skip(
        "needs an ed25519 signer (cryptography wheel or the isolated runner)",
        allow_module_level=True,
    )

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import numpy as np  # noqa: E402

import bench as _bench  # noqa: E402  (chain builder)

from tendermint_tpu.light import verifier as lv  # noqa: E402
from tendermint_tpu.light.batch import (  # noqa: E402
    HeaderRequest,
    fingerprint,
    group_stats,
    prepare_request,
)
from tendermint_tpu.light.service import (  # noqa: E402
    LightVerifyService,
    request_from_json,
    request_to_json,
)
from tendermint_tpu.observability import trace as tr  # noqa: E402
from tendermint_tpu.ops import epoch_cache as _epoch  # noqa: E402
from tendermint_tpu.ops import pipeline as pl  # noqa: E402
from tendermint_tpu.types import Fraction, SignedHeader  # noqa: E402
from tendermint_tpu.types.block import (  # noqa: E402
    BLOCK_ID_FLAG_ABSENT,
    Commit,
    CommitSig,
)
from tendermint_tpu.wire.canonical import Timestamp  # noqa: E402

CHAIN_ID = "light-svc-chain"
N_VALS = 8
N_HDRS = 6
PERIOD = 1e9
NOW = Timestamp(seconds=1_600_000_000 + N_HDRS + 60)


@pytest.fixture(scope="module")
def chain():
    return _bench._build_header_chain(CHAIN_ID, N_HDRS, N_VALS)


@pytest.fixture(scope="module")
def svc():
    _epoch.reset(4)
    v = pl.AsyncBatchVerifier(depth=2)
    s = LightVerifyService(verifier=v)
    yield s
    s.close()
    v.close()


def mkreq(chain, t, u, untrusted=None, period=PERIOD, **kw):
    return HeaderRequest(
        trusted_header=chain[t][0], trusted_vals=chain[t][1],
        untrusted_header=untrusted or chain[u][0],
        untrusted_vals=chain[u][1],
        trusting_period=period, **kw,
    )


def seq_verdict(req, now=NOW):
    """The sequential path's outcome as (type_name, str) or None."""
    try:
        lv.verify(req.trusted_header, req.trusted_vals,
                  req.untrusted_header, req.untrusted_vals,
                  req.trusting_period, now, req.max_clock_drift,
                  req.trust_level)
        return None
    except Exception as e:  # noqa: BLE001 — the verdict IS the error
        return (type(e).__name__, str(e))


def svc_verdict(svc, req, now=NOW):
    r = svc.submit(req, now=now)
    return None if r["ok"] else (r["error_type"], r["error"])


def assert_parity(svc, req, now=NOW, expect_type=None):
    want = seq_verdict(req, now)
    got = svc_verdict(svc, req, now)
    assert got == want
    if expect_type is not None:
        assert want is not None and want[0] == expect_type
    return want


def forge_commit(sh, lane, sig=b"\x07" * 64):
    c = Commit.decode(sh.commit.encode())
    c.signatures[lane] = dc_replace(c.signatures[lane], signature=sig)
    return SignedHeader(header=sh.header, commit=c)


class TestVerdictParity:
    def test_ok_adjacent_and_non_adjacent(self, chain, svc):
        assert svc_verdict(svc, mkreq(chain, 0, 1)) is None  # adjacent
        assert svc_verdict(svc, mkreq(chain, 0, 5)) is None  # skipping
        assert svc_verdict(svc, mkreq(chain, 2, 5)) is None

    def test_forged_commit_blame_parity(self, chain, svc):
        """Bad sigs must blame the same lane with the same string as the
        sequential verifier — the wrong-signature error carries the sig
        index and hex, so parity here is parity of the whole demux."""
        forged = forge_commit(chain[3][0], 4)
        req = mkreq(chain, 0, 3, untrusted=forged)
        want = assert_parity(svc, req, expect_type="ErrInvalidHeader")
        assert "wrong signature (#4)" in want[1]

    def test_forged_commit_in_trusting_prefix(self, chain, svc):
        """A tampered lane INSIDE the 1/3 early-stop prefix fails the
        trusting stage first — stage-order precedence must match."""
        forged = forge_commit(chain[3][0], 0)
        req = mkreq(chain, 0, 3, untrusted=forged)
        want = assert_parity(svc, req, expect_type="ErrInvalidHeader")
        assert "wrong signature (#0)" in want[1]

    def test_conflicting_header_same_height(self, chain, svc):
        """A forged header over the genuine commit (the same-height
        conflict shape): commit binding fails in validate_basic."""
        sh = chain[4][0]
        conflicted = SignedHeader(
            header=dc_replace(sh.header, app_hash=b"\x66" * 32),
            commit=sh.commit,
        )
        req = mkreq(chain, 0, 4, untrusted=conflicted)
        want = assert_parity(svc, req, expect_type="ErrInvalidHeader")
        assert "ValidateBasic failed" in want[1]

    def test_conflicting_header_resigned_minority(self, chain, svc):
        """A conflicting header RE-SIGNED by one validator (the lunatic
        shape a forging primary serves): insufficient trusted power."""
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.types import Vote
        from tendermint_tpu.types.block import BlockID, PartSetHeader
        from tendermint_tpu.types.vote import PRECOMMIT_TYPE

        sh, vset = chain[4]
        hdr = dc_replace(sh.header, app_hash=b"\x66" * 32)
        bid = BlockID(hash=hdr.hash(),
                      part_set_header=PartSetHeader(total=1, hash=hdr.hash()))
        # find the signer key for validator row 0 (builder seeds i+7)
        sks = {ed25519.gen_priv_key((i + 7).to_bytes(32, "little")).pub_key()
               .address(): ed25519.gen_priv_key((i + 7).to_bytes(32, "little"))
               for i in range(N_VALS)}
        sk = sks[vset.validators[0].address]
        v = Vote(type=PRECOMMIT_TYPE, height=hdr.height, round=0, block_id=bid,
                 timestamp=hdr.time,
                 validator_address=vset.validators[0].address,
                 validator_index=0)
        v = dc_replace(v, signature=sk.sign(v.sign_bytes(CHAIN_ID)))
        sigs = [v.to_commit_sig()] + [
            CommitSig.absent() for _ in range(N_VALS - 1)
        ]
        conflicted = SignedHeader(
            header=hdr,
            commit=Commit(height=hdr.height, round=0, block_id=bid,
                          signatures=sigs),
        )
        req = mkreq(chain, 0, 4, untrusted=conflicted)
        assert_parity(svc, req, expect_type="ErrNotEnoughTrust")

    def test_expired_trusted_header(self, chain, svc):
        req = mkreq(chain, 0, 5, period=1.0)
        want = assert_parity(svc, req, expect_type="ErrOldHeaderExpired")
        assert "old header has expired" in want[1]

    def test_trust_level_edge_exactly_one_third(self, chain, svc):
        """Exactly 1/3 of trusted power signing is NOT enough (the tally
        must EXCEED needed) — and one signer more flips the failing
        stage from trusting to the +2/3 check. Both orderings must match
        the sequential path byte-for-byte."""
        c3 = _bench._build_header_chain("edge-chain", 3, 3)
        for keep in (1, 2):
            sh = c3[2][0]
            commit = Commit.decode(sh.commit.encode())
            for lane in range(keep, 3):
                commit.signatures[lane] = CommitSig(
                    block_id_flag=BLOCK_ID_FLAG_ABSENT,
                    validator_address=b"", timestamp=Timestamp.zero(),
                    signature=b"",
                )
            thinned = SignedHeader(header=sh.header, commit=commit)
            req = HeaderRequest(
                trusted_header=c3[0][0], trusted_vals=c3[0][1],
                untrusted_header=thinned, untrusted_vals=c3[2][1],
                trusting_period=PERIOD,
            )
            want = assert_parity(
                svc, req,
                expect_type="ErrNotEnoughTrust" if keep == 1
                else "ErrInvalidHeader",
            )
            assert "voting power" in want[1]

    def test_height_not_greater(self, chain, svc):
        req = mkreq(chain, 3, 2)
        assert_parity(svc, req, expect_type="ErrInvalidHeader")

    def test_future_header_time_drift(self, chain, svc):
        # > max_clock_drift (10s) before chain[5]'s header time
        early = Timestamp(seconds=1_599_999_990)
        req = mkreq(chain, 0, 5)
        want = seq_verdict(req, early)
        assert want == svc_verdict(svc, req, early)
        assert want[0] == "ErrInvalidHeader" and "future" in want[1]


class TestServiceMechanics:
    def test_streaming_completion_order_covers_all_indices(self, chain, svc):
        reqs = [mkreq(chain, 0, k) for k in range(1, N_HDRS + 1)]
        batch = svc.submit_many(reqs, now=NOW)
        seen = [v["index"] for v in batch.stream(timeout=600)]
        assert sorted(seen) == list(range(len(reqs)))
        res = svc.submit_many(reqs, now=NOW).results(timeout=600)
        assert [r["index"] for r in res] == list(range(len(reqs)))
        assert all(r["ok"] for r in res)

    def test_memo_and_single_flight(self, chain, svc):
        req = mkreq(chain, 1, 5)
        s0 = svc.stats()
        r1 = svc.submit(req, now=NOW)
        # same fingerprint → memo hit, no new unique verification
        r2 = svc.submit(mkreq(chain, 1, 5), now=NOW)
        s1 = svc.stats()
        assert r1["ok"] and r2["ok"]
        assert s1["memo_hits"] >= s0["memo_hits"] + 1
        assert s1["unique"] == s0["unique"] + 1
        # a DIFFERENT now is a different verification (expiry depends on it)
        later = Timestamp(seconds=NOW.seconds + 1)
        assert fingerprint(req, NOW) != fingerprint(req, later)

    def test_unfingerprintable_requests_never_alias(self, chain, svc):
        """An incomplete header hashes to b'' (Header.hash's nil
        convention) — such requests must NOT share a memo/single-flight
        slot (review finding: two different b''-hash requests would
        alias one verdict). They verify uniquely instead."""
        sh, vset = chain[2]
        incomplete = SignedHeader(
            header=dc_replace(sh.header, validators_hash=b""),
            commit=sh.commit,
        )
        r1 = HeaderRequest(
            trusted_header=incomplete, trusted_vals=vset,
            untrusted_header=chain[4][0], untrusted_vals=chain[4][1],
            trusting_period=PERIOD,
        )
        r2 = HeaderRequest(
            trusted_header=SignedHeader(
                header=dc_replace(
                    sh.header, validators_hash=b"",
                    time=Timestamp(seconds=1),  # long expired
                ),
                commit=sh.commit,
            ),
            trusted_vals=vset,
            untrusted_header=chain[4][0], untrusted_vals=chain[4][1],
            trusting_period=PERIOD,
        )
        assert fingerprint(r1, NOW) is None and fingerprint(r2, NOW) is None
        s0 = svc.stats()
        got = [svc_verdict(svc, r) for r in (r1, r2)]
        s1 = svc.stats()
        assert s1["unique"] == s0["unique"] + 2  # no dedup, no memo
        assert s1["memo_hits"] == s0["memo_hits"]
        assert got[0] == seq_verdict(r1) and got[1] == seq_verdict(r2)
        assert got[0] != got[1]  # the aliasing bug would collapse these

    def test_service_clock_requests_dedup_across_calls(self, chain):
        """Requests that omit `now` must still share the memo across
        submit_many calls (review finding: a nanosecond-resolution
        service clock made every call's fingerprints unique). The
        resolved clock truncates to whole seconds — and the SAME
        truncated now drives verification, so key and verdict agree."""
        _epoch.reset(4)
        v = pl.AsyncBatchVerifier(depth=2)
        s = LightVerifyService(
            verifier=v,
            now_fn=lambda: Timestamp(seconds=NOW.seconds, nanos=123_456_789),
        )
        try:
            r1 = s.submit(mkreq(chain, 0, 4))  # no now anywhere
            r2 = s.submit(mkreq(chain, 0, 4))  # second CALL, same second
            assert r1["ok"] and r2["ok"]
            st = s.stats()
            assert st["unique"] == 1 and st["memo_hits"] == 1
        finally:
            s.close()
            v.close()

    def test_infra_failures_are_never_memoized(self, chain):
        """A pipeline-infrastructure failure (submit refused, dispatch
        died) must not become a sticky cached rejection — identical
        later requests re-verify (review finding: only parity verdicts
        are deterministic)."""

        class _FlakyVerifier:
            calls = 0

            def submit(self, entries, flow=None):
                _FlakyVerifier.calls += 1
                raise RuntimeError("verifier is closed")

        flaky = LightVerifyService(verifier=_FlakyVerifier())
        req = mkreq(chain, 0, 3)
        r1 = flaky.submit(req, now=NOW)
        assert not r1["ok"] and r1["error_type"] == "RuntimeError"
        r2 = flaky.submit(req, now=NOW)
        assert not r2["ok"]
        s = flaky.stats()
        # both attempts went through the full path: no memo entry, no hit
        assert s["unique"] == 2 and s["memo_hits"] == 0
        assert s["memo_entries"] == 0
        assert _FlakyVerifier.calls == 2
        flaky.close()

    def test_stream_deadline_raises_timeout(self):
        """stream(timeout) is an overall deadline: expiry surfaces as
        TimeoutError naming the pending count (never queue.Empty)."""
        from tendermint_tpu.light.service import VerdictBatch

        b = VerdictBatch(2)
        b._push({"index": 0, "ok": True})
        it = b.stream(timeout=0.05)
        assert next(it)["index"] == 0
        with pytest.raises(TimeoutError, match="1 of 2"):
            next(it)

    def test_epoch_grouping_metadata(self, chain):
        """Warm-epoch requests carry the valset's epoch key on every
        stage block — the coalescer's grouping input."""
        _epoch.reset(4)
        # first sight cold-registers the epoch and rides uncached (the
        # PR-5 contract); everything after is warm
        prepare_request(mkreq(chain, 0, 1), NOW)
        plans = [prepare_request(mkreq(chain, 0, k), NOW) for k in (2, 3, 4)]
        groups = group_stats(plans)
        # one warm epoch: every stage block shares one non-None key
        assert len(groups) == 1
        (key, count), = groups.items()
        assert key is not None and count == 6  # trusting+light per request

    def test_verdict_rows_are_owned_copies(self, chain, svc):
        """The service fans one device verdict row out to many waiters'
        conclude closures — rows must be host-owned (the PR-7 aliasing
        contract extended to the serving layer)."""
        plan = prepare_request(mkreq(chain, 0, 4), NOW)
        stages = plan.entry_stages()
        futs = [svc._v.submit(st.entries) for st in stages]
        rows = [np.array(f.result(timeout=600), dtype=bool) for f in futs]
        assert all(r.flags.owndata for r in rows)

    def test_flow_chain_rpc_arrival_to_verdict(self, chain, svc):
        tr.TRACER.clear()
        tr.configure(enabled=True)
        try:
            # fresh fingerprint (unseen height pair) so the request goes
            # through the full unique-verification path
            r = svc.submit(mkreq(chain, 2, 4), now=NOW)
            assert r["ok"]
        finally:
            tr.configure(enabled=False)
        chains = tr.flow_chains(tr.TRACER.export_chrome())
        light = [
            evs for evs in chains.values()
            if evs and evs[0]["name"] == "light.rpc_arrival"
        ]
        assert light, "no light-service flow chain recorded"
        names = [e["name"] for e in light[-1]]
        assert names[0] == "light.rpc_arrival"
        assert "pipeline.submit" in names
        assert names[-1] == "light.verdict"
        phases = [(e["args"] or {}).get("flow_phase") for e in light[-1]]
        assert phases[0] == "s" and phases[-1] == "f"


class _StubNode:
    """Environment(node) double for the endpoint test: /light_verify is
    self-contained and never touches the node's stores."""

    config = None


class TestRPCEndpoint:
    @pytest.fixture(scope="class")
    def server(self):
        from tendermint_tpu.rpc.core import Environment
        from tendermint_tpu.rpc.server import RPCServer

        env = Environment(_StubNode())
        srv = RPCServer("127.0.0.1:0", env)
        srv.start()
        yield srv
        srv.stop()

    def _post(self, srv, payload):
        req = urllib.request.Request(
            f"http://{srv.listen_addr}/", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    def test_roundtrip_batch(self, chain, server):
        reqs = [request_to_json(mkreq(chain, 0, k)) for k in (1, 3, 5)]
        forged = forge_commit(chain[3][0], 4)
        reqs.append(request_to_json(mkreq(chain, 0, 3, untrusted=forged)))
        # pin now so the verdict matches the sequential reference
        for d in reqs:
            d["now"] = request_to_json(
                mkreq(chain, 0, 1, now=NOW)
            )["now"]
        res = self._post(server, {
            "jsonrpc": "2.0", "id": 1, "method": "light_verify",
            "params": {"requests": reqs},
        })
        out = res["result"]
        assert out["total"] == "4" and out["ok_count"] == "3"
        by_idx = {v["index"]: v for v in out["verdicts"]}
        assert by_idx[3]["ok"] is False
        want = seq_verdict(mkreq(chain, 0, 3, untrusted=forged))
        assert (by_idx[3]["error_type"], by_idx[3]["error"]) == want

    def test_json_codec_roundtrip_preserves_fingerprint(self, chain):
        req = mkreq(chain, 0, 4, now=NOW)
        rt = request_from_json(
            json.loads(json.dumps(request_to_json(req)))
        )
        assert fingerprint(rt, NOW) == fingerprint(req, NOW)

    def test_streaming_ndjson(self, chain, server):
        import urllib.parse

        reqs = [request_to_json(mkreq(chain, 0, k)) for k in (2, 4)]
        q = urllib.parse.quote(json.dumps(reqs))
        with urllib.request.urlopen(
            f"http://{server.listen_addr}/light_verify?requests={q}"
            "&stream=true", timeout=600,
        ) as r:
            assert r.headers.get("Content-Type") == "application/x-ndjson"
            lines = [json.loads(l) for l in r.read().splitlines() if l]
        assert lines[-1]["done"] is True and lines[-1]["total"] == 2
        verdicts = lines[:-1]
        assert sorted(v["index"] for v in verdicts) == [0, 1]
        assert all(v["ok"] for v in verdicts)

    def test_bad_request_is_rpc_error(self, server):
        res = self._post(server, {
            "jsonrpc": "2.0", "id": 2, "method": "light_verify",
            "params": {"requests": [{"trusted_header": {}}]},
        })
        assert "error" in res and res["error"]["code"] == -32602


N_CLIENTS = 220


class TestSimnetE2E:
    """The acceptance scenario: 200+ simulated clients against a
    rotating-valset cluster, adversarial clients rejected with
    sequential-parity errors, merged flight-recorder trace with
    complete RPC-arrival → verdict chains."""

    @pytest.fixture(scope="class")
    def cluster_run(self):
        from tendermint_tpu.simnet import Cluster, rotation_schedule

        faults = rotation_schedule(
            n_nodes=5, n_validators=4, every=4, start=4, until=10
        )
        c = Cluster(n_nodes=5, n_validators=4, seed=7, faults=faults,
                    tracing=True)
        try:
            rep = c.run_to_height(12, max_virtual_s=600.0)
            yield c, rep
        finally:
            c.stop()

    def test_light_fleet_against_churn_cluster(self, cluster_run):
        from tendermint_tpu.light.provider import NodeBackedProvider

        c, rep = cluster_run
        assert rep.ok, rep.violations
        assert rep.valset_changes, "rotation never changed the valset"
        node = c.nodes[0]
        provider = NodeBackedProvider(node.bstore, node.sstore)
        tip = node.bstore.height() - 1  # commits exist below the tip
        blocks = {h: provider.light_block(h) for h in range(1, tip + 1)}
        now = Timestamp(
            seconds=blocks[tip].signed_header.header.time.seconds + 60
        )

        def req_for(t, u, untrusted=None):
            return HeaderRequest(
                trusted_header=blocks[t].signed_header,
                trusted_vals=blocks[t].validators,
                untrusted_header=untrusted or blocks[u].signed_header,
                untrusted_vals=blocks[u].validators,
                trusting_period=PERIOD,
            )

        # honest fleet: every client skip-verifies 2 headers in its
        # trust window (trusted height varies → several epoch groups)
        honest = []
        for cl in range(N_CLIENTS):
            t = 1 + cl % 3
            u1 = t + 1 + cl % (tip - t - 1)
            u2 = tip - cl % 2
            honest.append(req_for(t, u1))
            honest.append(req_for(t, max(u2, t + 1)))
        # adversarial clients: forged commits + conflicting headers
        forged_sh = forge_commit(blocks[tip - 1].signed_header, 1)
        conflicted = SignedHeader(
            header=dc_replace(
                blocks[tip].signed_header.header, app_hash=b"\x66" * 32
            ),
            commit=blocks[tip].signed_header.commit,
        )
        bad = []
        for _ in range(8):
            bad.append(req_for(1, tip - 1, untrusted=forged_sh))
            bad.append(req_for(1, tip, untrusted=conflicted))

        _epoch.reset(8)
        v = pl.AsyncBatchVerifier(depth=2)
        svc = LightVerifyService(verifier=v)
        tr.TRACER.clear()
        tr.configure(enabled=True)
        try:
            batch = svc.submit_many(honest + bad, now=now)
            res = batch.results(timeout=900)
            stats = svc.stats()
        finally:
            tr.configure(enabled=False)
            svc.close()
            v.close()

        n_honest = len(honest)
        assert all(r["ok"] for r in res[:n_honest]), [
            r for r in res[:n_honest] if not r["ok"]
        ][:3]
        # adversarial verdicts: rejected, byte-identical to sequential
        want_forged = seq_verdict(req_for(1, tip - 1, untrusted=forged_sh), now)
        want_conf = seq_verdict(req_for(1, tip, untrusted=conflicted), now)
        assert want_forged is not None and want_conf is not None
        for i, r in enumerate(res[n_honest:]):
            want = want_forged if i % 2 == 0 else want_conf
            assert (r["error_type"], r["error"]) == want
        # the fleet amortized: far fewer unique verifications than
        # requests, across the rotation's several validator sets
        assert stats["requests"] == len(honest) + len(bad)
        assert stats["unique"] < stats["requests"] // 4
        assert stats["memo_hits"] + stats["inflight_joins"] > 0
        plans = [prepare_request(req_for(1 + k % 3, tip - k % 2), now)
                 for k in range(6)]
        # the rotation's validator sets differ by a key or two: they map
        # onto ONE device table (ops/epoch_cache.py), so their stages fuse
        # where they used to form a group a set
        assert len(group_stats(plans)) >= 1
        assert _epoch.stats()["tables_shared"] >= 1, \
            "the rotation's sets share no table"

        # merged flight recorder: cluster doc + service doc share flow
        # namespaces; every unique verification's chain is COMPLETE
        merged = tr.merge_traces(
            [c.export_merged_trace(), tr.TRACER.export_chrome()],
            labels=["cluster", "light-service"],
        )
        chains = tr.flow_chains(merged)
        complete = [
            evs for evs in chains.values()
            if evs[0]["name"] == "light.rpc_arrival"
            and evs[-1]["name"] == "light.verdict"
        ]
        assert len(complete) == stats["unique"]
        # the cluster's own gossip→verify chains coexist in the doc
        assert any(
            evs[0]["name"] == "gossip.send" for evs in chains.values()
        )
