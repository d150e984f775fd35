"""int8 error-feedback gradient sync under shard_map over 4 devices.

The cross-pod data-parallel all-reduce is the compression target
(parallel/compression.py).  This test runs the real collective path:
4 host devices, per-shard gradients, compressed psum — and checks (a) the
reduced value approximates the true mean within one quantisation step and
(b) error feedback keeps the *accumulated* drift bounded over many steps.
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel import compression as comp

    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)  # per-worker grads

    def sync(g, e):
        out, ne = comp.compressed_psum_tree({"g": g}, {"g": e},
                                            axis_name="data")
        return out["g"], ne["g"]

    # jax.make_mesh builds explicit axes, whose shard_map bodies need the
    # mesh in context
    step_fn = jax.jit(jax.shard_map(sync, mesh=mesh,
                                    in_specs=(P("data"), P("data")),
                                    out_specs=(P("data"), P("data"))))

    def shmap(g, e):
        with jax.set_mesh(mesh):
            return step_fn(g, e)

    err = jnp.zeros((4, 64), jnp.float32)
    acc = jnp.zeros((64,), jnp.float32)
    true_acc = jnp.zeros((64,), jnp.float32)
    for step in range(30):
        g = G * (1.0 + 0.1 * step)
        out, err = shmap(g, err)
        # every shard received the same mean
        o = np.asarray(out)
        np.testing.assert_allclose(o[0], o[1], atol=1e-6)
        acc = acc + o[0]
        true_acc = true_acc + np.asarray(g).mean(0)
        step_size = float(np.abs(np.asarray(g)).max()) / 127.0
        np.testing.assert_allclose(o[0], np.asarray(g).mean(0),
                                   atol=2.0 * step_size)
    # error feedback: accumulated drift stays ~one quantisation step
    drift = np.abs(np.asarray(acc - true_acc)).max()
    bound = 4.0 * float(np.abs(np.asarray(G)).max() * 4.0) / 127.0
    assert drift < bound, (drift, bound)
    print("COMPRESS-OK")
    """
)


def test_compressed_allreduce_four_workers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "COMPRESS-OK" in out.stdout, out.stdout + out.stderr
