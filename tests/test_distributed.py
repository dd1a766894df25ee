"""Distribution correctness on 8 virtual host devices (subprocess — the
device-count flag must be set before jax initializes).

Covers: sharded train step == single-device result, MoE shard_map path,
decode under a mesh, and checkpoint resharding (elastic restart).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.training import init_train_state, make_train_step, make_decode_step
    from repro.sharding import rules as shrules
    from repro.launch import specs as S
    from repro.configs.base import ShapeConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))

    for arch in ["smollm_135m", "mixtral_8x22b", "jamba_1_5_large_398b"]:
        cfg = get_reduced(arch).replace(dtype="float32", microbatches=2)
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)}
        step = make_train_step(model, AdamWConfig(lr=1e-2))
        # single device reference
        s1, m1 = jax.jit(step)(state, batch)
        # sharded
        with shrules.axis_rules(mesh, fsdp=False):
            shapes = jax.eval_shape(lambda s, b: step(s, b), state, batch)
            sh = jax.jit(step)
            s2, m2 = sh(state, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 2e-3, (arch, d, float(m1["loss"]), float(m2["loss"]))
        print(arch, "sharded==single loss ok", float(m1["loss"]), d)

    # decode under mesh with cache shardings
    cfg = get_reduced("smollm_135m").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("t", 16, 4, "decode")
    cache = model.init_decode_state(4, 16, jnp.float32)
    with shrules.axis_rules(mesh):
        cshard = S.decode_cache_shardings(jax.eval_shape(lambda: cache), cfg, shape, mesh)
        cache_sharded = jax.tree_util.tree_map(lambda x, s: jax.device_put(x, s), cache, cshard)
        dstep = jax.jit(make_decode_step(model))
        tok = jnp.zeros((4, 1), jnp.int32)
        nxt, logits, cache2 = dstep(params, cache_sharded, {"token": tok})
        nxt2, logits2, _ = jax.jit(make_decode_step(model))(params, cache, {"token": tok})
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2), atol=1e-4)
    print("decode sharded ok")

    # elastic reshard: save sharded, restore on a (4,2) mesh
    from repro.checkpoint import save_pytree, restore_pytree
    from repro.checkpoint.reshard import reshard_to_mesh
    import tempfile
    d = tempfile.mkdtemp()
    save_pytree(params, d)
    mesh2 = make_mesh((4, 2), ("data", "model"))
    restored = restore_pytree(params, d)
    resharded = reshard_to_mesh(restored, mesh2)
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(resharded)[0]
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1))
    print("reshard ok")
    print("ALL_DISTRIBUTED_OK")
    """
)


@pytest.mark.slow
def test_distributed_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # 8 virtual CPU devices; never the host's TPU
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        timeout=1200,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "ALL_DISTRIBUTED_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]
