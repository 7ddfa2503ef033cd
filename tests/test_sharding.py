"""Sharding-rule tests. Rule logic is pure (PartitionSpec construction +
divisibility fallback) and testable on a real multi-device mesh built in a
SUBPROCESS with --xla_force_host_platform_device_count=8 (the main test
process keeps the single real CPU device, per the dry-run contract)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import registry as REG
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as SH


def _mesh1():
    return make_mesh((1, 1), ("data", "model"))


def test_fallback_drops_indivisible_axes():
    mesh = _mesh1()
    # axis size 1 divides everything -> spec preserved
    assert SH.fallback(P("data", "model"), (7, 13), mesh) == \
        P("data", "model")


def test_param_rules_cover_every_leaf():
    """Every param leaf of every arch gets a VALID spec (divisible dims)."""
    mesh = _mesh1()
    for arch in REG.ARCH_IDS:
        cfg = REG.get_config(arch)
        params = REG.params_specs(cfg)
        shardings = SH.param_shardings(mesh, params)
        assert len(jax.tree.leaves(shardings)) == \
            len(jax.tree.leaves(params))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import registry as REG
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as SH

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    # 1. every full-scale arch: all specs valid on the mesh
    for arch in REG.ARCH_IDS:
        cfg = REG.get_config(arch)
        params = REG.params_specs(cfg)
        sh = SH.param_shardings(mesh, params)
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        pflat = jax.tree_util.tree_flatten_with_path(params)[0]
        for (path, s), (_, spec) in zip(flat, pflat):
            for dim, axes in zip(spec.shape, s.spec):
                if axes is None:
                    continue
                size = SH._axis_size(mesh, axes)
                assert dim % size == 0, (arch, path, spec.shape, s.spec)

    # 2. rules: wq is (FSDP, TP); wo transposed; norms replicated
    cfg = REG.get_config("yi-9b")
    params = REG.params_specs(cfg)
    sh = SH.param_shardings(mesh, params)
    l0 = sh["layers"]["l0"]
    def norm(spec):  # PartitionSpec modulo trailing Nones
        t = tuple(spec)
        while t and t[-1] is None:
            t = t[:-1]
        return t

    assert norm(l0["mixer"]["wq"].spec) == (None, "data", "model")
    assert norm(l0["mixer"]["wo"].spec) == (None, "model", "data")
    assert norm(l0["norm1"].spec) == ()
    assert norm(sh["embed"].spec) == ("model", "data")

    # 3. batch sharding composes pod+data on the batch dim
    batch = REG.batch_specs(cfg, REG.get_shape("train_4k"))
    bs = SH.batch_shardings(mesh, batch)
    assert bs["tokens"].spec == P(("pod", "data"), None)

    # 4. cache: B=1 long-context falls back to sharding the KV sequence
    cache = REG.cache_specs(REG.get_config("jamba-1.5-large-398b"),
                            REG.get_shape("long_500k"))
    cs = SH.cache_shardings(mesh, cache)
    kv = cs["l3"]["k"].spec
    assert kv[1] is None and kv[2] == ("pod", "data", "model"), kv

    # 5. a sharded matmul with these rules runs and matches unsharded
    w = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8)
    x = jnp.arange(4 * 16, dtype=jnp.float32).reshape(4, 16)
    wsh = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
    xsh = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"), None)))
    y = jax.jit(lambda x, w: x @ w)(xsh, wsh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w))
    print("SUBPROC_OK")
""")


def test_rules_on_8_device_mesh():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=420,
                       cwd="/root/repo")
    assert "SUBPROC_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
