"""The delta rule's kernels compiled for a described TPU v5e, without a chip
(the `on-chip-measurement` guide's third rehearsal): ``bf_kda_fwd`` and
``bf_kda_bwd`` at the published head of 128 in bfloat16, chunks of 64, at
the cell's row of 4096 tokens and at ``model_check``'s of 1024.  The TPU's
compiler is installed here and raises what the chip's would: a slice that
is not aligned to the tiling, a kernel that overfills VMEM.  The interpreter
that tier-1 runs the kernels in passes both.  Nothing runs: no result, no
time.

The topology is described inside a module fixture and nowhere at import:
one process at a time loads the TPU's library."""

import os

import jax
import jax.numpy as jnp
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bluefog_tpu.ops import kda  # noqa: E402

HEADS, HEAD, CHUNK = 32, 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the runtime raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache and
    # cannot be read back without one
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def operands(seq, sharding):
    """The shapes ``_rule`` hands the kernels for one row of ``seq``."""
    n = seq // CHUNK
    shape = lambda s, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=sharding)
    wide = shape((1, seq, HEADS * HEAD), jnp.bfloat16)
    return dict(
        q=wide, k=wide, v=wide, g=shape(wide.shape, jnp.float32),
        beta=shape((1, HEADS, n, 1, CHUNK), jnp.float32),
        states=shape((1, HEADS, n, HEAD, HEAD), jnp.float32),
        dims=(n, kda._step_chunks(n), CHUNK, HEAD, HEAD))


@pytest.mark.parametrize("seq", [4096, 1024])
@pytest.mark.parametrize("kernel", ["bf_kda_fwd", "bf_kda_fwd+states",
                                    "bf_kda_bwd"])
def test_the_rule_kernels_compile_for_a_v5e(one_chip, kernel, seq):
    x = operands(seq, one_chip)
    static = dict(dims=x["dims"], interpret=False, vma=frozenset())
    five = (x["q"], x["k"], x["v"], x["g"], x["beta"])
    if kernel == "bf_kda_bwd":
        lowered = kda._bwd_call.lower(*five, x["states"], x["v"], **static)
    else:
        lowered = kda._fwd_call.lower(*five, save="states" in kernel,
                                      **static)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert kernel.split("+")[0] in text


def test_the_door_names_the_shapes_no_tile_takes():
    """What ``kda_chunked`` asks on a TPU before it stages a kernel."""
    kda.check_tileable(CHUNK, HEAD, HEAD)
    kda.check_tileable(16, 256, 128)
    for chunk, dk, dv in ((64, 64, 128), (64, 128, 96), (8, 128, 128)):
        with pytest.raises(ValueError, match=f"head of {dk} keys and {dv} "
                           f"values in chunks of {chunk}"):
            kda.check_tileable(chunk, dk, dv)
