"""The RLC kernel under shard_map over the 8-device virtual mesh (its
own traced program; see tests/_rlc.py)."""

import pytest

pytest.importorskip("jax")

from _rlc import _deterministic_z, _sign_batch  # noqa: E402,F401


class TestShardedRlc:
    @pytest.mark.time_limit(600)  # 156-199 s on a cold cache
    def test_sharded_rlc_matches_host_oracle(self):
        """The flagship RLC kernel under shard_map over the 8-device
        virtual mesh: lane-sharded dp, psum voting-power tally of
        accepted lanes, host fallback restores per-sig blame and adds
        the rejected lane's valid power back — totals must match the
        per-sig oracle exactly."""
        import jax

        from tendermint_tpu.crypto import _edwards as E
        from tendermint_tpu.ops import sharded

        mesh = sharded.make_mesh(min(8, len(jax.devices())))
        entries = _sign_batch(22, tamper={9})
        powers = [100 + i for i in range(22)]
        valid, tallied, all_valid = sharded.verify_commit_sharded_rlc(
            entries, powers, mesh
        )
        expect = [E.verify_zip215(p, m, s) for p, m, s in entries]
        assert valid.tolist() == expect == [i != 9 for i in range(22)]
        assert not all_valid
        assert tallied == sum(p for i, p in enumerate(powers) if i != 9)

    def test_sharded_rlc_all_valid(self):
        import jax

        from tendermint_tpu.ops import sharded

        mesh = sharded.make_mesh(min(8, len(jax.devices())))
        entries = _sign_batch(16)
        powers = [7] * 16
        valid, tallied, all_valid = sharded.verify_commit_sharded_rlc(
            entries, powers, mesh
        )
        assert valid.all() and all_valid and tallied == 7 * 16
