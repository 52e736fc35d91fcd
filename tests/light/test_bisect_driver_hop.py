"""The skipping cell's driver (benchmark/drivers/light_bisect_from_wire.py)
through the device path, with a hop as ISSUE 36 made it: on a
100-validator, 80-header chain with the epoch cache on, a catch-up's two
hops each send the trusting third and the +2/3 as ONE launch of 34 + 67
signatures, nothing is verified on the host, and the driver's own check()
and benchmark/run.py's cell loop pass. What
tests/benchmark/test_benchmark_bisect.py::
test_two_thirds_on_the_device_and_the_third_on_the_host held before the
change (tests/conftest.py PINS_THE_THIRD_TO_THE_HOST), at the same size,
seed and cache depth."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, spec  # noqa: E402

FX100 = {"name": "fx100", "validators": 100, "voting_power": 100,
         "chain_id": "bench-fx100", "headers": 80,
         "keys_replaced_per_height": 1, "block_interval_s": 60,
         "trusting_period_s": 86400, "max_clock_drift_s": 10,
         "trusted_height": 1, "target_height": 80, "trust_level": "1/3",
         "witnesses": 1, "now_s_after_btime": 4800}
SEED = 2 ** 31 + 7
QUIET = {"generator": {"kind": "closed_loop", "callers": 1}}
# the root's +2/3 check, then 1 -> 45 -> 80 (1 -> 80 refused)
SIGS = 67 + 2 * (34 + 67)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax

    from tendermint_tpu.ops import epoch_cache

    # the cell walks 22 sets over 8 tables, again and again: here 3 over 2,
    # so that the LRU has dropped a set's table before its next request
    epoch_cache.reset(depth=2)
    driver = spec.load_driver(os.path.join(ROOT, "benchmark"),
                              "light_bisect_from_wire")
    try:
        yield driver.session_class()(
            FX100, SEED, str(tmp_path_factory.mktemp("checkout")),
            jax.devices(), lambda m: None)
    finally:
        epoch_cache.reset()


def _rise(s, fn):
    c0 = s.counters()
    out = fn()
    c1 = s.counters()
    return out, {k: c1[k] - c0[k] for k in c1
                 if isinstance(c1[k], int) and c1[k] != c0[k]}


@pytest.mark.time_limit(600)
def test_the_third_rides_the_two_thirds_launch(session):
    from tendermint_tpu.libs.metrics import ops_stats

    s = session
    fused = ops_stats()["light_hops_fused"]
    _none, warm = _rise(s, lambda: s.warm(QUIET, lambda m: None))
    got, rose = _rise(s, lambda: s.request(0))
    assert got == SIGS
    for r, n in ((warm, 2), (rose, 1)):
        assert r["sigs_verified_device"] == n * SIGS
        assert r["launches"] == n * 3
        assert r["light_trusting_sigs_device"] == n * 2 * 34
        assert not {"sigs_verified_host", "host_fallback_batches",
                    "light_trusting_sigs_host", "dispatch_errors"} & set(r)
        # no hop's set maps onto a resident table and none is found again:
        # every +2/3 check is a cold epoch, and a hop whose new set is cold
        # does not look the old one up (nothing would gather from it)
        assert r["epoch_tables_built"] == n * 3 == r["epoch_cache_misses"]
        assert "epoch_tables_shared" not in r and "epoch_cache_hits" not in r
    assert ops_stats()["light_hops_fused"] - fused == 3 * 2
    assert s.check() == []


@pytest.mark.time_limit(600)
def test_the_cell_loop_runs_the_driver_on_the_device_path(session, monkeypatch):
    """benchmark/run.py's own loop over the cell's files, with the
    80-header chain in place of the configuration: `correct`, with the
    hops' thirds on the device."""
    cell = spec.load_cell(ROOT, "bisect100-catchup1")
    cell.config = FX100
    cell.traffic = dict(cell.traffic, settle_s=0.2)
    monkeypatch.setattr(cell.driver, "open", lambda *a: session)
    monkeypatch.setattr(spec, "load_cell", lambda root, name: cell)
    lines = []
    res = run.run_cell(ROOT, "bisect100-catchup1", SEED, 4.0, False,
                       started=time.time(), say=lines.append)
    assert res["correct"] is True and res["failed"] == 0, lines
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"commit_p50_ms", "setup_s"}
    assert not any("CHECK FAILED" in ln for ln in lines)
