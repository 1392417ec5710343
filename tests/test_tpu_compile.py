"""Every Pallas kernel compiles for a TPU v5e chip at a real bucket size.

The interpret-mode tests (test_kernels.py, test_wire_formats.py) check what
the kernels compute; they cannot see what the TPU compiler refuses: blocks
not aligned to the native tiles, casts and reductions Mosaic does not
lower, shape casts it cannot lay out.  These tests compile each kernel with
``interpret=False`` for a described ``v5e:2x2`` topology (no chip needed)
at a 4M-element bucket, W=4 gathered workers for the wire kernels.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file."""

import pytest

N = 4 * 1024 * 1024  # elements per bucket
W = 4  # gathered workers for the wire-reduce kernels
LANES = 128
ROWS = N // LANES


@pytest.fixture(scope="module")
def one_chip():
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _cases():
    """name -> (kernel call, [(shape, dtype name)]) at the shapes the
    ``kernels.ops`` wrappers hand the kernels for an N-element bucket."""
    from repro.kernels import (qsgd, qsgd_ef, sign_pack, terngrad,
                               threshold_sparsify, wire_reduce, wkv6)

    x2, s11 = ((ROWS, LANES), "float32"), ((1, 1), "float32")
    words = ROWS // sign_pack.BITS  # 32 signs per 32-bit word
    tern_words = ROWS // wire_reduce.TERN_SLOTS  # 16 two-bit slots per word
    weights = ((W, LANES), "float32")
    bh, seq, hd = 64, 4096, 64  # wkv6: B*H rows of a 4096-token sequence
    return {
        "qsgd_2d": (qsgd.qsgd_2d, [x2, x2, s11, s11]),
        "qsgd_ef_2d": (qsgd_ef.qsgd_ef_2d, [x2, x2, x2, s11, s11, s11]),
        "terngrad_2d": (terngrad.terngrad_2d, [x2, x2, s11]),
        "sign_pack_2d": (sign_pack.sign_pack_2d, [x2]),
        "sign_unpack_2d": (sign_pack.sign_unpack_2d, [((words, LANES), "int32")]),
        "sign_vote_3d": (wire_reduce.sign_vote_3d,
                         [((W, words, LANES), "int32"), weights]),
        "tern_pack_2d": (wire_reduce.tern_pack_2d, [((ROWS, LANES), "int8")]),
        "tern_acc_3d": (wire_reduce.tern_acc_3d,
                        [((W, tern_words, LANES), "int32"), weights]),
        "int8_acc_3d": (wire_reduce.int8_acc_3d,
                        [((W, ROWS, LANES), "int8"), weights]),
        "threshold_2d": (threshold_sparsify.threshold_2d, [x2, s11]),
        "wkv6_chunked": (wkv6.wkv6_chunked,
                         [((bh, seq, hd), "float32")] * 4
                         + [((bh, 1, hd), "float32"), ((bh, hd, hd), "float32")]),
    }


KERNELS = ("qsgd_2d", "qsgd_ef_2d", "terngrad_2d", "sign_pack_2d",
           "sign_unpack_2d", "sign_vote_3d", "tern_pack_2d", "tern_acc_3d",
           "int8_acc_3d", "threshold_2d", "wkv6_chunked")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax

    kernel, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(lambda *a: kernel(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
