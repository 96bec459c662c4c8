"""Compressed cross-pod gradient all-reduce: correctness of the mean, the
elementwise residual bound (the paper's guarantee as a systems property),
the overflow fallback, and end-to-end training equivalence.

Needs >1 device for the 'pod' axis -> runs in a subprocess with
xla_force_host_platform_device_count (the main pytest process already
locked jax to 1 CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compression.grads import (GradCompressionConfig,
                                         compressed_mean,
                                         compressed_mean_tree)

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names={"pod"},
                             check_vma=False)

    cfg = GradCompressionConfig(eb_rel=2.0 ** -8, bin_bits=8,
                                outlier_cap_frac=1 / 16)

    rng = np.random.default_rng(0)
    g_global = rng.standard_normal((2, 4096)).astype(np.float32)
    g_global[0, 7] = 90.0      # outlier in pod 0's gradient
    g_global[1, 9] = -70.0

    def podwise(g):
        mean, resid = compressed_mean(g, cfg, "pod")
        return mean, resid

    mapped = smap(podwise, P("pod", None),
                  (P("pod", None), P("pod", None)))
    gd = jax.device_put(jnp.asarray(g_global),
                        NamedSharding(mesh, P("pod", None)))
    mean, resid = jax.jit(mapped)(gd)
    mean = np.asarray(mean)
    resid = np.asarray(resid)

    true_mean = g_global.mean(axis=0)
    # both pods must hold the SAME mean
    assert np.array_equal(mean[0], mean[1]), "pods disagree on the mean"
    # each pod's contribution error is bounded by its eb -> mean error
    # bounded by mean of ebs
    ebs = [cfg.eb_rel * np.sqrt(np.mean(g_global[i] ** 2)) for i in (0, 1)]
    tol = float(np.mean(ebs)) * 1.01
    err = np.abs(mean[0] - true_mean)
    assert err.max() <= tol, (err.max(), tol)
    # outliers shipped EXACTLY: at index 7 the error comes only from pod1's
    # quantization
    assert err[7] <= ebs[1] * 0.51, "outlier slot not exact"
    # residual elementwise bound (error feedback is provably small)
    for i in (0, 1):
        assert np.abs(resid[i]).max() <= ebs[i] * 1.01
    print("MEAN_OK")

    # overflow path: tensor with > cap outliers falls back lossless
    g2 = np.zeros((2, 1024), np.float32)
    g2[:, :600] = rng.standard_normal((2, 600)) * 1000  # huge spread
    g2[:, 600:] = rng.standard_normal((2, 424)) * 1e-6
    cfg2 = GradCompressionConfig(eb_rel=2.0 ** -16, bin_bits=8,
                                 outlier_cap_frac=1 / 256)
    g2d = jax.device_put(jnp.asarray(g2), NamedSharding(mesh, P("pod", None)))
    mapped2 = smap(lambda g: compressed_mean(g, cfg2, "pod"),
                   P("pod", None), (P("pod", None), P("pod", None)))
    m2, r2 = jax.jit(mapped2)(g2d)
    m2 = np.asarray(m2)
    np.testing.assert_allclose(m2[0], g2.mean(0), rtol=1e-6)  # lossless
    assert np.abs(np.asarray(r2)).max() == 0.0
    print("OVERFLOW_OK")

    # tree version with error feedback accumulates unbiased-ly
    tree = {"a": jnp.asarray(g_global), "b": jnp.asarray(g_global * 0.5)}
    resid0 = jax.tree.map(jnp.zeros_like, tree)
    mapped3 = smap(
        lambda t, r: compressed_mean_tree(t, r, cfg, "pod"),
        ({"a": P("pod", None), "b": P("pod", None)},) * 2,
        ({"a": P("pod", None), "b": P("pod", None)},) * 2)
    tree_d = jax.tree.map(lambda x: jax.device_put(
        x, NamedSharding(mesh, P("pod", None))), tree)
    m3, r3 = jax.jit(mapped3)(tree_d, resid0)
    assert np.isfinite(np.asarray(m3["a"])).all()
    print("TREE_OK")
""")


@pytest.mark.slow
def test_compressed_pod_allreduce():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("MEAN_OK", "OVERFLOW_OK", "TREE_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr)
