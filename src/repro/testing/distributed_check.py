"""Multi-device substrate checks: compressed psum, elastic resharding,
cross-mesh checkpoint restore.  Run via ``python -m`` (8 simulated devices).
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_CHECK_DEVICES", "8")
    + " "
    + os.environ.get("XLA_FLAGS", "")
)

import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.compat import make_mesh, shard_map  # noqa: E402


def check_compressed_psum():
    from repro.optim.compress import compressed_psum_ef

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g_all = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)  # per-device rows
    exact_mean = np.asarray(g_all).mean(axis=0)

    def local(g, e):
        grads = {"w": g[0]}
        efs = {"w": e[0]}
        out, new_e = compressed_psum_ef(grads, efs, axis_name="data")
        return out["w"][None], new_e["w"][None]

    fn = jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), check_vma=False,
        )
    )
    e = jnp.zeros((8, 64), jnp.float32)
    # one step: quantized mean close to exact; EF bounds the residual
    out, e = fn(g_all, e)
    got = np.asarray(out)[0]
    scale = np.abs(np.asarray(g_all)).max() / 127.0
    np.testing.assert_allclose(got, exact_mean, atol=scale + 1e-6)
    # convergence with EF: average of transmitted means over repeats -> exact
    acc = np.zeros(64, np.float32)
    n = 30
    for _ in range(n):
        out, e = fn(g_all, e)
        acc += np.asarray(out)[0]
    np.testing.assert_allclose(acc / n, exact_mean, atol=scale / 4 + 1e-6)
    print("PASS compressed psum (int8 + error feedback, 8-way)")


def check_elastic_reshard():
    from repro.runtime.elastic import shrink_mesh, reshard
    from repro.sharding.rules import params_shardings

    devs = jax.devices()
    mesh8 = shrink_mesh(devs, model_axis=4)  # (2,4)
    assert dict(mesh8.shape) == {"data": 2, "model": 4}
    params = {
        "layers": {"w": jnp.arange(8 * 16, dtype=jnp.float32).reshape(1, 8, 16)},
        "embed": {"table": jnp.arange(32 * 4, dtype=jnp.float32).reshape(32, 4)},
    }
    sh8 = params_shardings(params, mesh8)
    p8 = reshard(params, sh8)
    # lose half the devices -> (1,4) mesh
    mesh4 = shrink_mesh(devs[:4], model_axis=4)
    assert dict(mesh4.shape) == {"data": 1, "model": 4}
    sh4 = params_shardings(params, mesh4)
    p4 = reshard(p8, sh4)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # 3 devices: policy maximizes utilized devices, shrinking the model
    # axis as needed (SP degree 1 = plain DP is still a valid config).
    mesh3 = shrink_mesh(devs[:3], model_axis=4)
    assert dict(mesh3.shape) == {"data": 3, "model": 1}
    print("PASS elastic reshard (8 -> 4 -> 3 devices)")


def check_checkpoint_cross_mesh():
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.elastic import shrink_mesh, reshard
    from repro.sharding.rules import params_shardings

    devs = jax.devices()
    mesh_a = shrink_mesh(devs, model_axis=4)  # (2,4)
    tree = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)}
    tree_a = reshard(tree, params_shardings(tree, mesh_a))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=1)
        mgr.save(1, tree_a)
        # restore onto a DIFFERENT mesh shape
        mesh_b = shrink_mesh(devs, model_axis=2)  # (4,2)
        sh_b = params_shardings(tree, mesh_b)
        restored = mgr.restore(1, jax.tree.map(jnp.zeros_like, tree), shardings=sh_b)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
        assert restored["w"].sharding.mesh.shape["model"] == 2
    print("PASS checkpoint restore across meshes (2x4 -> 4x2)")


CHECKS = {
    "compress": check_compressed_psum,
    "elastic": check_elastic_reshard,
    "ckpt_mesh": check_checkpoint_cross_mesh,
}


def main(argv):
    names = argv[1:] or list(CHECKS)
    assert len(jax.devices()) >= 8
    for n in names:
        CHECKS[n]()
    print("ALL CHECKS PASSED")


if __name__ == "__main__":
    main(sys.argv)
