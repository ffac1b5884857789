"""Sharded compaction across devices.

The test spawns a subprocess with 8 virtual host devices: the XLA
device-count flag must be set before jax initializes, so it cannot run
in the test process.
"""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np

assert len(jax.devices()) == 8

from repro.core import compaction, formats, offload
from repro.core.formats import SSTGeometry

geom = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=1024,
                   sst_bytes=8192)
mesh = jax.make_mesh((8,), ("data",))

def entries_for_shard(s):
    # disjoint key ranges per shard
    items = [(b"%02d-key%04d" % (s, i), i + 1, b"v%d" % i)
             for i in range(64)]
    keys = np.stack([formats.pack_key_bytes(k, geom.key_bytes)
                     for k, _, _ in items])
    meta = np.array([(q << 1) | 1 for _, q, _ in items], np.uint32)
    vals = np.stack([formats.pack_value_bytes(v, geom.value_bytes)
                     for _, _, v in items])
    return jnp.asarray(keys), jnp.asarray(meta), jnp.asarray(vals)

imgs = [offload.build_image(*entries_for_shard(s), geom=geom)
        for s in range(8)]
img = formats.concat_images(imgs)
img_sharded = offload.place_sharded(img, mesh, ("data",))
out_s, stats_s = offload.sharded_compact(img_sharded, mesh, ("data",),
                                          geom=geom, sort_mode="xla")
# reference: per-shard single-device compaction
for s in range(8):
    ref_out, _ = compaction.compact(imgs[s], geom=geom, sort_mode="xla")
    nb = imgs[s].keys.shape[0]
    got = jax.tree.map(lambda a: np.asarray(a), out_s)
    for f in ("keys", "meta", "vals", "shared", "nvalid", "crc", "bloom"):
        a = getattr(got, f)[s * nb:(s + 1) * nb]
        b = np.asarray(getattr(ref_out, f))
        np.testing.assert_array_equal(a, b, err_msg=f)
print("OK sharded_compact")
"""


@pytest.mark.slow
def test_sharded_compact_matches_single_device(tmp_path):
    """``place_sharded`` + ``sharded_compact`` over 8 devices give each
    shard exactly what ``compaction.compact`` gives it alone."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = tmp_path / "multidev.py"
    script.write_text(SCRIPT)
    r = subprocess.run([sys.executable, str(script)], cwd=os.getcwd(),
                       env=env, capture_output=True, text=True,
                       timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK sharded_compact" in r.stdout
