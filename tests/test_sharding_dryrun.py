"""Sharded-compile tests on a small virtual-device mesh.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(never set globally — smoke tests must see 1 device).  They exercise the same
bundle builders the 512-device dry-run uses, at miniature scale, plus the
roofline extraction and multi-device train-step numerics.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_sub(code: str, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.splitlines()[-1])


def test_small_mesh_train_compile_and_roofline():
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.launch.steps import make_cell
        from repro.launch.roofline import analyze_compiled, model_flops_for
        cfg = get_config("qwen3-0.6b").reduced()
        shape = ShapeConfig("t", 64, 8, "train")
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        with mesh:
            b = make_cell(cfg, shape, mesh)
            compiled = b.fn.lower(*b.args).compile()
        rl = analyze_compiled("t", compiled, None,
                              model_flops_for(cfg, shape), 8,
                              "TPU v5 lite")
        rec = rl.to_dict()
        print(json.dumps({"flops": rec["flops_per_device"],
                          "coll": rec["coll_bytes_per_device"],
                          "bneck": rec["bottleneck"]}))
    """)
    rec = _run_sub(code)
    assert rec["flops"] > 0
    assert rec["coll"] > 0           # FSDP/TP collectives must exist
    assert rec["bneck"] in ("compute", "memory", "collective")


def test_small_mesh_decode_and_prefill_compile():
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.launch.steps import make_cell
        out = {}
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        for arch in ("smollm-135m", "mamba2-370m"):
            cfg = get_config(arch).reduced()
            for kind, name in (("prefill", "p"), ("decode", "d")):
                shape = ShapeConfig(name, 128, 4, kind)
                with mesh:
                    b = make_cell(cfg, shape, mesh)
                    b.fn.lower(*b.args).compile()
                out[f"{arch}/{kind}"] = True
        print(json.dumps(out))
    """)
    rec = _run_sub(code)
    assert len(rec) == 4 and all(rec.values())


def test_multidevice_train_numerics_match_single():
    """A sharded train step must produce the same loss as single-device."""
    code = textwrap.dedent("""
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.data.pipeline import DataConfig, make_batch
        from repro.models import model_api
        from repro.nn.params import default_rules, tree_sharding
        from repro.launch.steps import get_param_axes, fit_batch_rules

        cfg = get_config("smollm-135m").reduced().replace(
            compute_dtype="float32")
        api = model_api(cfg)
        params, _ = api.init_params(jax.random.PRNGKey(0))
        batch_np = make_batch(DataConfig(vocab=cfg.vocab, seq_len=32,
                                         global_batch=8), 0)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()
                 if k != "mask"}
        loss_single = float(api.loss_fn(params, batch)[0])

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        rules = fit_batch_rules(default_rules(), 8, mesh)
        p_axes = get_param_axes(cfg)
        with mesh:
            shardings = tree_sharding(p_axes, rules, mesh)
            params_s = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                    params, shardings)
            loss_sharded = float(jax.jit(
                lambda p, b: api.loss_fn(p, b, rules)[0])(params_s, batch))
        print(json.dumps({"single": loss_single, "sharded": loss_sharded}))
    """)
    rec = _run_sub(code)
    assert rec["single"] == pytest.approx(rec["sharded"], rel=2e-4)


def test_production_mesh_requires_devices():
    """make_production_mesh must refuse to build without enough devices."""
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(RuntimeError):
        make_production_mesh()           # this process has 1 CPU device


def test_roofline_peaks_by_device_kind():
    from repro.core.hw import TPU_V5E, chip_for
    assert chip_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="cpu"):
        chip_for("cpu")


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set and receives the entries;
    otherwise the cache goes to the fixed in-checkout directory."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = textwrap.dedent("""
        import os
        from repro.launch.cli import DEFAULT_COMPILE_CACHE, enable_compile_cache
        d = enable_compile_cache()
        if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            import jax, jax.numpy as jnp
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()
        else:
            assert d == str(DEFAULT_COMPILE_CACHE), d
        print(d)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    d = out.stdout.split()[-1]
    if env_dir:
        assert d == str(tmp_path)
        assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())
    else:
        assert d == str(REPO / ".jax_cache")
