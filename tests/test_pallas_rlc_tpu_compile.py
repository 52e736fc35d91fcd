"""The RLC pipelines at their REAL shapes through the TPU's own compiler,
for a v5e that is described and not attached (no chip, nothing runs):
what interpret mode cannot refuse — a block that overflows the scoped
VMEM, a slice off the tiling. One shape per lane width, the ones the
benchmark's cells launch, and the sr25519 ristretto kernel at the two
smallest buckets of its ladder. All in this one file: only one process
at a time may hold the TPU's library (tests/_rlc.py has the kernels'
verdicts)."""

import pytest

pytest.importorskip("jax")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for an absent chip is written to the
    # persistent cache and can never be read back: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


# (signatures of the launch, validator-table columns): hub150's commit,
# a 400-signature one, max10k's commit
SHAPES = [(150, 256), (400, 512), (10_000, 16_384)]


@pytest.mark.time_limit(420)
@pytest.mark.parametrize("n,vp", SHAPES, ids=["m2", "m4", "m8"])
def test_cached_pipeline_compiles_for_v5e(one_chip, n, vp):
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import pallas_rlc as pr

    bucket, g, block, m = pr.plan_bucket(n)
    assert block == pr.BLOCK_LANES == 128

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    f = pr._jitted_rlc_verify_cached(m, g, block, vp, False)
    compiled = f.lower(
        arg((4 * 32, vp)), arg((1, vp)),
        arg((pr.packed_layout(bucket, m)[-1],)),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # K1, K2, K3 are kernels
    assert f"rlc_verify_cached_g{g}_m{m}_b128_vp{vp}" in text


@pytest.mark.time_limit(420)
def test_table_patch_compiles_for_v5e_and_leaves_its_arguments(one_chip):
    """The program that places keys appended to a resident table
    (ops/epoch_cache.py, the churning chain's every request): a 128-row
    table and ONE packed buffer of MIN_PATCH_ROWS rows to scatter. No
    argument is donated, so a launch in flight keeps the table value it
    was given."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import epoch_cache as ec

    vp, k = 128, ec.MIN_PATCH_ROWS

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = ec._coords_patch_fn().lower(
        arg((4 * 32, vp)), arg((1, vp)), arg((k * (4 * 32 + 2),))).compile()
    text = compiled.as_text()
    assert "epoch_coords_patch" in text
    assert "input_output_alias" not in text.split("ENTRY")[0], \
        "the patch donates a table"
    assert "while" not in text, "a patch decompresses nothing on the device"
    out_coords, out_ok = compiled.out_info
    assert out_coords.shape == (4 * 32, vp) and out_ok.shape == (1, vp)


@pytest.mark.time_limit(420)
def test_churn_cells_cached_pipeline_compiles_for_v5e(one_chip):
    """67 signatures of a 100-validator set: bucket 128 at m=2, 64 lanes
    in one block, over a 128-column table."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import pallas_rlc as pr

    bucket, g, block, m = pr.plan_bucket(67)
    assert (bucket, g, block, m) == (128, 64, 64, 2)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = pr._jitted_rlc_verify_cached(m, g, block, 128, False).lower(
        arg((4 * 32, 128)), arg((1, 128)),
        arg((pr.packed_layout(bucket, m)[-1],))).compile()
    assert "rlc_verify_cached_g64_m2_b64_vp128" in compiled.as_text()


@pytest.mark.time_limit(420)
def test_bisect_cells_uncached_pipeline_compiles_for_v5e(one_chip):
    """A skipping hop's launch of 101 signatures from a set no table
    holds: bucket 128 at m=2, its ONE packed buffer (public keys at the
    head) split and laid slot-major on the device before K1. The
    buffer is donated."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import pallas_rlc as pr

    bucket, g, block, m = pr.plan_bucket(101)
    assert (bucket, g, block, m) == (128, 64, 64, 2)
    words = pr.packed_layout(bucket, m, pr.PUB_WORDS)[-1]
    assert 4 * words == 132 * bucket
    compiled = pr._jitted_rlc_verify(m, g, block, False, donate=True).lower(
        jax.ShapeDtypeStruct((words,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # K1, K2, K3 are kernels
    assert "rlc_verify_g64_m2_b64" in text


@pytest.mark.time_limit(420)
@pytest.mark.parametrize("n", [70, 150], ids=["b128", "b256"])
def test_sr25519_kernel_compiles_for_v5e(one_chip, n):
    """The ristretto kernel as select_kernel's sr25519 arm launches it:
    bucket and block from the lane's own ladder (150 signatures, the
    cell's commit, in 256 lanes of one block)."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import backend
    from tendermint_tpu.ops import pallas_sr25519 as ps
    from tendermint_tpu.ops.pallas_verify import pick_block

    bucket = backend._sr_bucket_for(n)
    block = pick_block(bucket)
    assert block == bucket == {70: 128, 150: 256}[n]

    compiled = ps._jitted_sr25519_verify(bucket, block, False).lower(
        jax.ShapeDtypeStruct((ps.PACKED_ROWS, bucket), jnp.int32,
                             sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # K1r, K2, K3r
    assert f"sr25519_verify_n{bucket}_b{block}" in text
