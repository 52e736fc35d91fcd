"""The sr25519 cell's yardstick on the CPU. Its plain reference
(benchmark/reference_sr25519.py): Keccak-f[1600] against hashlib's
SHA3-256, ristretto255 against the draft-irtf-cfrg-ristretto255 small
multiples, merlin challenges against the program's native transcript,
signatures both ways against the program's crypto/sr25519, forgeries
refused by both. Its data builder is deterministic in the seed and its
cache round-trips. Its driver, on a 12-validator set through the device
path (the ristretto kernel in interpret mode), counts every signature on
the device and passes its own check, which fails on a wrong verdict or a
signature on the host."""

import hashlib
import importlib.util
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import data_sr25519, reference_sr25519 as ref, spec  # noqa: E402

FX = {"name": "fxsr", "validators": 12, "voting_power": 100,
      "chain_id": "bench-fxsr", "pool_commits": 4}
SEED = 2 ** 31 + 17
CELL = "sr150-lastcommit1"


def _spec_multiples():
    path = os.path.join(ROOT, "tests", "test_sr25519.py")
    s = importlib.util.spec_from_file_location("_sr25519_vectors", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.SPEC_MULTIPLES


# -- the reference -----------------------------------------------------------------


@pytest.mark.parametrize("msg", [b"", b"abc", bytes(range(200)) * 3])
def test_keccak_permutation_gives_sha3_256(msg):
    rate = 136
    padded = bytearray(msg + b"\x06" + bytes(-(len(msg) + 1) % rate))
    padded[-1] |= 0x80
    st = bytearray(200)
    for i in range(0, len(padded), rate):
        for j in range(rate):
            st[j] ^= padded[i + j]
        ref.keccak_f1600(st)
    assert bytes(st[:32]) == hashlib.sha3_256(msg).digest()


def test_ristretto_small_multiples_encode_and_decode():
    pt = ref.IDENTITY
    for i, want in enumerate(_spec_multiples()):
        enc = bytes.fromhex(want)
        assert ref.encode(pt) == enc, f"multiple {i}"
        assert ref.equal(ref.decode(enc), pt), f"multiple {i}"
        pt = ref.add(pt, ref.BASE)
    odd = bytearray(bytes.fromhex(_spec_multiples()[1]))
    odd[0] |= 1
    assert ref.decode(bytes(odd)) is None            # negative s
    assert ref.decode(b"\xff" * 32) is None          # not below p
    assert ref.decode((2).to_bytes(32, "little")) is None  # not a point


def test_merlin_challenges_equal_the_native_transcript():
    from tendermint_tpu import native
    from tendermint_tpu.crypto import sr25519

    mod = native.load()
    if mod is None:
        pytest.skip("no native module")
    rng = random.Random(SEED)
    n = 9
    pubs = [rng.randbytes(32) for _ in range(n)]
    rs = [rng.randbytes(32) for _ in range(n)]
    msgs = [rng.randbytes(rng.randrange(0, 300)) for _ in range(n)]
    import numpy as np

    offs = np.cumsum([0] + [len(m) for m in msgs]).astype(np.int64)
    k = mod.sr25519_challenges_buf(sr25519.SIGNING_CTX, b"".join(pubs),
                                   b"".join(rs), b"".join(msgs), offs.tobytes())
    assert [int.from_bytes(k[32 * i:32 * i + 32], "little")
            for i in range(n)] == [ref.challenge(p, m, r)
                                   for p, m, r in zip(pubs, msgs, rs)]


def test_signatures_verify_both_ways_and_forgeries_fail_both():
    from tendermint_tpu.crypto import sr25519

    rng = random.Random(SEED + 1)
    x, pub = ref.keypair(b"ref key")
    sk = sr25519.gen_priv_key(bytes(range(32)))
    prog_pub = sk.pub_key().bytes()
    for i in range(3):
        msg = rng.randbytes(40 + i)
        ours = ref.sign(x, pub, msg, rng.randbytes(32))
        theirs = sk.sign(msg)
        assert sr25519.verify(pub, msg, ours) and ref.verify(pub, msg, ours)
        assert ref.verify(prog_pub, msg, theirs)
        assert sr25519.verify_batch([(pub, msg, ours),
                                     (prog_pub, msg, theirs)]) == [True, True]
        for sig, key in ((ours, pub), (theirs, prog_pub)):
            forged = bytearray(sig)
            forged[rng.randrange(63)] ^= 1 << rng.randrange(8)
            unmarked = bytearray(sig)
            unmarked[63] &= 0x7F
            for bad_sig, bad_msg in ((bytes(forged), msg),
                                     (bytes(unmarked), msg),
                                     (sig, msg + b"!")):
                assert not ref.verify(key, bad_msg, bad_sig)
                assert not sr25519.verify(key, bad_msg, bad_sig)


# -- the data builder ---------------------------------------------------------------


def test_same_seed_same_bytes_and_the_pool_cache_round_trips(tmp_path):
    first = data_sr25519.pool(str(tmp_path), FX, SEED)
    again = data_sr25519.pool(str(tmp_path), FX, SEED)
    built = data_sr25519.build(FX, SEED)
    assert first.built and not again.built
    assert again.commits == first.commits == built.commits
    assert (again.pubkeys == built.pubkeys).all()
    assert [(c.what, c.height, c.wire, c.expect) for c in again.blame] == \
        [(c.what, c.height, c.wire, c.expect) for c in built.blame]
    assert [c.expect[0] for c in built.blame] == [
        "ValueError", "ValueError", "ErrNotEnoughVotingPowerSigned"]
    assert data_sr25519.build(FX, SEED + 1).commits[0] != built.commits[0]


def test_the_pool_decodes_to_the_programs_commit_and_sign_bytes():
    from benchmark import wire
    from tendermint_tpu.types.block import Commit

    pool = data_sr25519.build(FX, SEED)
    c = Commit.decode(pool.commits[0])
    assert c.encode() == pool.commits[0]
    tpl = wire.sign_bytes_template(FX["chain_id"], 1, pool.digests[0])
    for idx in (0, 11):
        sig = c.signatures[idx]
        msg = c.vote_sign_bytes(FX["chain_id"], idx)
        assert msg == wire.sign_bytes(tpl, sig.timestamp.seconds,
                                      sig.timestamp.nanos)
        assert ref.verify(bytes(pool.pubkeys[idx]), msg, sig.signature)


# -- the cell and its driver ---------------------------------------------------------


def pin_the_cell(root):
    """The cell as a checkout at `root` declares it: its driver, its
    configuration and the metrics it reports, which a later PR may add
    to, but not take from."""
    cell = spec.load_cell(root, CELL)
    assert cell.driver.__name__.endswith("sr_commit_from_wire")
    assert cell.config["key_type"] == "sr25519" and cell.config["reduced"] == []
    assert [m["name"] for m, _d in cell.end_to_end] == ["commit_p50_ms",
                                                         "setup_s"]
    names = {m["name"] for m, _d in cell.per_layer}
    assert {"decode_us_per_sig.lat", "prep_us_per_sig.lat",
            "transfer_us_per_sig.lat", "kernel_wait_p50_ms.lat",
            "device_idle_share.lat", "h2d_bytes_per_sig.lat",
            "host_verified_share.lat", "kernel_us_per_sig.lat",
            "sr_challenge_us_per_sig.lat", "sr_fill_us_per_sig.lat"} <= names
    # the epoch cache is not on this path: its metrics would read nothing
    assert not any(n.startswith("epoch_") for n in names)


def test_the_cell_loads_by_name_and_no_harness_code_names_it():
    pin_the_cell(ROOT)
    bdir = os.path.join(ROOT, "benchmark")
    for path in (os.path.join(bdir, f) for f in (
            "reference_sr25519.py", "data_sr25519.py", "roofline_sr25519.py",
            os.path.join("drivers", "sr_commit_from_wire.py"))):
        code = open(path).read()
        for name in (CELL, "commit_p50_ms", "sigs_per_s"):
            assert name not in code, (path, name)


# one second, two commits of 150 signatures, one launch each: the prep 1 ms
# a launch with the challenges (0.3 ms) and the fill (0.2 ms) inside it, the
# kernel 0.6 ms on the device under the profiler's and the ledger's name and
# 0.1 ms of an ed25519 launch beside it
SR_STRETCH = {
    "t_a": 0.0, "t_b": 1.0, "sigs": 300, "spans_recorded": 8,
    "ring_capacity": 262144,
    "device_events": [
        ["/device:TPU:0", "XLA Ops", "%sr25519_verify_n256_b256.3", 0.20, 0.0006],
        ["/device:TPU:0", "XLA Ops", "_sr25519_verify_n256_b256", 0.70, 0.0006],
        ["/device:TPU:0", "XLA Ops", "%rlc_verify_cached.5", 0.90, 0.0001]],
    "spans": [
        ["pipeline.prep", 0.1000, 0.1010, 2],
        ["ops.sr_prep.challenges", 0.1001, 0.1004, 2],
        ["ops.sr_prep.challenges.native", 0.1001, 0.1004, 2],
        ["ops.sr_prep.fill", 0.1005, 0.1007, 2],
        ["pipeline.prep", 0.6000, 0.6010, 2],
        ["ops.sr_prep.challenges", 0.6001, 0.6004, 2],
        ["ops.sr_prep.challenges.native", 0.6001, 0.6004, 2],
        ["ops.sr_prep.fill", 0.6005, 0.6007, 2],
    ],
}
SR_COUNTERS = {"before": {"sigs_verified_device": 0, "sigs_verified_host": 0,
                          "launches": 0},
               "after": {"sigs_verified_device": 300, "sigs_verified_host": 0,
                         "launches": 2}}
# the prep's stages nest inside pipeline.prep and count once; both kernel
# launches and the ed25519 one are busy time, and kernel time of both
# schemes; every signature on the device
SR_METRICS = {"prep_us_per_sig.lat": 2 * 1000.0 / 300,
              "sr_challenge_us_per_sig.lat": 2 * 300.0 / 300,
              "sr_fill_us_per_sig.lat": 2 * 200.0 / 300,
              "kernel_us_per_sig.lat": (2 * 600.0 + 100.0) / 300,
              "device_idle_share.lat": 100.0 * (1 - 0.0013),
              "sigs_per_launch.lat": 150.0,
              "host_verified_share.lat": 0.0}


@pytest.mark.parametrize("name", sorted(SR_METRICS))
def test_the_cells_metrics_read_the_sr25519_path(name):
    import json

    from benchmark import readers

    (entry,) = [m for m in spec.manifest(ROOT)["per_layer"]
                if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "commit_p50_ms"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        d = json.load(f)
    got = readers.read(d, {"trace": SR_STRETCH, "counters": SR_COUNTERS})
    assert got == pytest.approx(SR_METRICS[name])


def test_the_launch_is_counted_from_its_shape():
    from benchmark import roofline_sr25519 as rf

    ops = rf.sr25519_verify_ops(256)
    lane = rf.per_lane()
    assert ops["fe_mul"] == 256 * lane["fe_mul"] > 0
    assert ops["fe_sq"] == 256 * lane["fe_sq"] > 0
    # the ladder is most of it: 127 steps of two doublings and an addition
    assert lane["fe_sq"] > 127 * 8 and lane["fe_mul"] > 127 * 14
    assert rf.sr25519_verify_bytes(512) == 2 * rf.sr25519_verify_bytes(256)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax

    from tendermint_tpu.ops import backend

    mp = pytest.MonkeyPatch()
    mp.setenv("TM_TPU_PALLAS", "1")
    backend.engine.cache_clear()
    driver = spec.load_driver(os.path.join(ROOT, "benchmark"),
                              "sr_commit_from_wire")
    root = str(tmp_path_factory.mktemp("checkout"))
    try:
        yield driver.session_class()(FX, SEED, root, jax.devices(),
                                     lambda m: None)
    finally:
        mp.undo()
        backend.engine.cache_clear()


@pytest.mark.time_limit(480)
def test_the_driver_counts_every_signature_on_the_device(session):
    s = session
    assert s.n_sigs == 12 and s.n_pool == 4
    c0 = s._ops_stats()
    s.warm({"generator": {"kind": "closed_loop", "callers": 1}}, print)
    assert sum(s.request(i) for i in range(s.n_pool)) == 4 * 12
    c1 = s._ops_stats()
    assert c1["sr25519_sigs_device"] - c0["sr25519_sigs_device"] == 7 * 12
    assert c1["sr25519_launches"] - c0["sr25519_launches"] == 7
    assert c1["sr25519_sigs_host"] == c0["sr25519_sigs_host"]
    assert s.counters()["sigs_per_request"] == 12


@pytest.mark.time_limit(480)
def test_the_drivers_check_passes_and_fails_where_it_should(session,
                                                          monkeypatch):
    s = session
    assert s.check() == []
    real = s._verify

    def accept_all(*a):
        try:
            real(*a)
        except ValueError:
            pass

    monkeypatch.setattr(s, "_verify", accept_all)
    bad = s.check()
    assert len(bad) == 3 and all("raised None" in b for b in bad), bad
    monkeypatch.setattr(s, "_verify", real)
    stats = s._ops_stats
    monkeypatch.setattr(s, "_ops_stats", lambda: dict(
        stats(), sr25519_sigs_host=stats()["sr25519_sigs_host"] + 1))
    assert any("on the host" in b for b in s.check())
    # a program that keeps no sr25519 counters is checked on its verdicts
    monkeypatch.setattr(s, "_ops_stats", lambda: {
        k: v for k, v in stats().items() if not k.startswith("sr25519_")})
    assert s._sr_counters() is None
