"""Launch-layer tests: partition specs, mesh construction (subprocess with
512 fake devices -- main test process keeps 1 device per the mandate),
and step building + abstract lowering on the production mesh."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest


from repro import configs
from repro.launch import partition
from jax.sharding import PartitionSpec as P

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_lm_param_specs_match_tree():
    from repro.models import transformer as tf
    for arch in ("qwen3-14b", "moonshot-v1-16b-a3b"):
        cfg = configs.get(arch).smoke_config()
        params = jax.eval_shape(
            lambda: tf.init(jax.random.PRNGKey(0), cfg))

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        specs = partition.lm_param_specs(cfg, FakeMesh())
        # same tree structure => every param has a spec
        jax.tree.map(lambda sds, sp: None, params, specs,
                     is_leaf=lambda x: isinstance(x, P))


def test_divisibility_fallbacks():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    cfg = configs.get("qwen3-14b").config()
    specs = partition.lm_param_specs(cfg, FakeMesh())
    # vocab 151936 % 16 == 0 -> embed sharded on model
    assert specs["embed"][0] == "model"
    # kv dim 8*128=1024 % 16 == 0 -> sharded
    assert specs["layers"]["wk"][2] == "model"


PROD_MESH_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    from repro.launch import mesh as mesh_lib, steps
    m1 = mesh_lib.make_production_mesh()
    assert m1.devices.shape == (16, 16), m1.devices.shape
    m2 = mesh_lib.make_production_mesh(multi_pod=True)
    assert m2.devices.shape == (2, 16, 16)
    assert m2.axis_names == ("pod", "data", "model")
    # build + LOWER (not compile: compile is the dry-run's job) a few cells
    for arch, shape in [("gatedgcn", "molecule"),
                        ("mind", "serve_p99"),
                        ("smscc", "community_query")]:
        b = steps.build(arch, shape, m2)
        with m2:
            jax.jit(b.fn, in_shardings=b.in_shardings,
                    out_shardings=b.out_shardings).lower(*b.args)
    # skipped long-context cells return None
    assert steps.build("qwen3-14b", "long_500k", m1) is None
    print("MESH_OK")
""")


def test_production_mesh_and_lowering_subprocess():
    """512-device mesh construction + sharded lowering in a subprocess
    (keeps this process at 1 device)."""
    r = subprocess.run([sys.executable, "-c", PROD_MESH_TEST],
                       capture_output=True, text=True, timeout=540,
                       env={"PYTHONPATH": "src",
                            "PATH": "/usr/bin:/bin",
                            # without it libtpu probes for a TPU on a
                            # host that has none, which takes minutes
                            "JAX_PLATFORMS": "cpu"},
                       cwd=ROOT)
    assert "MESH_OK" in r.stdout, r.stderr[-2000:]


def test_main_process_single_device():
    assert len(jax.devices()) == 1  # smoke tests must see 1 device


def test_dryrun_collective_parser():
    from repro.launch import dryrun
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), replica_groups={}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%add
  %rs = f32[16]{0} reduce-scatter(f32[256]{0} %z), dimensions={0}
    """
    out = dryrun.collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2     # result side (gathered)
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 256 * 4     # operand side (pre-reduce)
    assert out["count_all-reduce"] == 1


ELASTIC_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt import checkpoint

    ckpt_dir = sys.argv[1]
    tree_like = {"w": jnp.zeros((16, 4)), "m": jnp.zeros((16, 4)),
                 "step": jnp.zeros((), jnp.int32)}
    restored, step = checkpoint.restore(ckpt_dir, tree_like)
    assert step == 3, step
    # place the restored (host) arrays onto a 4x2 mesh the ORIGINAL
    # single-device run never saw -- the elastic-restart path
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    w = jax.device_put(restored["w"],
                       NamedSharding(mesh, P("data", "model")))
    assert len(w.sharding.device_set) == 8
    np.testing.assert_array_equal(
        np.asarray(w), np.arange(64, dtype=np.float32).reshape(16, 4))
    print("ELASTIC_OK")
""")


def test_elastic_restore_different_mesh(tmp_path):
    """Checkpoint written by a 1-device run restores onto an 8-device
    (4x2) mesh: shardings are axis-name trees, so only device placement
    changes (elasticity per DESIGN.md §5)."""
    import jax.numpy as jnp
    from repro.ckpt import checkpoint
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(16, 4),
            "m": jnp.ones((16, 4)), "step": jnp.int32(3)}
    checkpoint.save(str(tmp_path), 3, tree)
    r = subprocess.run(
        [sys.executable, "-c", ELASTIC_TEST, str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert "ELASTIC_OK" in r.stdout, r.stderr[-1500:]
