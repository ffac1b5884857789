"""Compile every main-path Pallas kernel for a described TPU v5e chip.

No chip is attached: the TPU compiler in the installed JAX compiles for a
v5e topology that is only described, which refuses what the chip's
compiler would refuse (Mosaic lowering gaps, block shapes off the (8, 128)
tiling, too much VMEM).  Shapes are the paper's geometry (16 B keys,
128 B values, 4 KB blocks, 4 MB SSTs, 10 bloom bits per key) at the sizes
a 4-SST compaction and a 1,024-candidate ``multi_get`` wave present.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.  The kernels are called with ``interpret=False``
because ``default_interpret()`` sees the CPU here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.luda_paper import PAPER
from repro.kernels import bloom, crc32, lookup, merge_path, prefix

GEOM = PAPER.geometry(128)
BLOCKS = 4096          # 4 SSTs of 1,024 blocks
CANDIDATES = 1024
MERGE_RUNS = (16384,) * 4
K, L, VW = GEOM.block_kvs, GEOM.key_lanes, GEOM.value_words
W = GEOM.bloom_words(K)
P = GEOM.bloom_probes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def u32(*shape):
    return shape, jnp.uint32


KERNELS = {
    "crc32": (lambda *s: crc32.crc32_blocks_sections(list(s),
                                                     interpret=False),
              [u32(BLOCKS, 1), u32(BLOCKS, K * L), u32(BLOCKS, K),
               u32(BLOCKS, K * VW), u32(BLOCKS, K)]),
    "bloom_build": (lambda k, v: bloom.bloom_build(
        k, v, n_words=W, n_probes=P, interpret=False),
        [u32(BLOCKS, K, L), u32(BLOCKS, K)]),
    "prefix_encode": (lambda k: prefix.prefix_encode(
        k, restart_interval=GEOM.restart_interval, interpret=False),
        [u32(BLOCKS * K, L)]),
    "merge_path": (lambda r: merge_path.merge_runs(r, MERGE_RUNS,
                                                   interpret=False),
                   [u32(sum(MERGE_RUNS), L + 2)]),
    "multi_probe": (lambda f, k: bloom.multi_probe(f, k, n_probes=P,
                                                   interpret=False),
                    [u32(CANDIDATES, W), u32(CANDIDATES, L)]),
    "lookup_blocks": (lambda k, m, v, n, q: lookup.lookup_blocks(
        k, m, v, n, q, interpret=False),
        [u32(CANDIDATES, K, L), u32(CANDIDATES, K), u32(CANDIDATES, K, VW),
         ((CANDIDATES,), jnp.int32), u32(CANDIDATES, L)]),
    "bloom_query": (lambda f, k: bloom.bloom_query(f, k, n_probes=P,
                                                   interpret=False),
                    [u32(BLOCKS, W), u32(BLOCKS, 8, L)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compaction_program_names_for_v5e(one_chip, monkeypatch):
    """The benchmark's device-trace readers match on these names: the
    compaction program is ``jit_compact`` and its kernels are named
    ``merge_runs`` and ``crc32_blocks_sections``."""
    import re

    from repro.core import compaction
    from repro.core.formats import SSTImage
    from repro.kernels import common
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    blocks = 16
    img = SSTImage(*(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                     for shape, dtype in (
                         u32(blocks, K, L), u32(blocks, K),
                         u32(blocks, K, VW), ((blocks, K), jnp.int32),
                         ((blocks,), jnp.int32), u32(blocks), u32(1, 1))))
    text = compaction.compact.lower(
        img, geom=GEOM, sort_mode="merge", backend="pallas",
        run_lens=(blocks // 2 * K,) * 2).compile().as_text()
    assert text.startswith("HloModule jit_compact,")
    for kernel in ("merge_runs", "crc32_blocks_sections"):
        assert re.search(rf"^\s*(ROOT )?%{kernel}(\.\d+)? = .*"
                         r'custom_call_target="tpu_custom_call"', text,
                         re.M), kernel
