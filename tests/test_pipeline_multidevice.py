"""Sharded two-pass pipeline over 8 forced host devices: bit-identity.

Pass 1 (pruning bound + device compaction) and pass 2 (MC + diameter
sub-batches) both shard over the mesh's ``data`` axis.  This test runs the
real collective path -- 8 host CPU devices, ``shard_map`` pass 1, sharded
``jit`` pass 2 -- on the Pallas 'interpret' backend and checks the feature
rows are **bit-identical** to the unsharded single-device run (batches are
padded to the data-axis multiple with duplicate rows, so per-case kernel
shapes never change).  The mesh is delivered via the ambient
``use_mesh`` context to cover the BatchedExtractor's mesh pickup.  Same
subprocess pattern as tests/test_compression_multidevice.py.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.tier1

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_AUTOTUNE"] = "0"
    import jax, numpy as np
    from repro.core.pipeline import BatchedExtractor
    from repro.parallel.sharding import use_mesh
    from repro.data.synthetic import make_case

    assert jax.device_count() == 8, jax.device_count()
    cases = [make_case((18, 16, 14), seed=s) for s in (1, 2, 3)]
    cases.append((np.zeros((8, 8, 8), np.float32),
                  np.zeros((8, 8, 8), np.float32), (1.0, 1.0, 1.0)))

    base, bstats = BatchedExtractor(backend="interpret").run(cases)
    assert bstats["data_parallel"] == 1

    mesh = jax.make_mesh((8,), ("data",))
    with use_mesh(mesh):
        bx = BatchedExtractor(backend="interpret")
    assert bx.mesh is mesh  # picked up from the ambient use_mesh context
    sharded, sstats = bx.run(cases)
    assert sstats["data_parallel"] == 8
    assert sstats["empty_cases"] == bstats["empty_cases"] == 1

    for i, (a, b) in enumerate(zip(base, sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"case {i}")
    print("SHARDED-PIPELINE-OK")
    """
)


def test_sharded_two_pass_bit_identical_eight_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "SHARDED-PIPELINE-OK" in out.stdout, out.stdout + out.stderr
