"""Multi-device SPMD semantics, via subprocesses (the only place outside the
dry-run allowed to force host platform devices)."""
import subprocess
import sys
import textwrap

import pytest


def run_py(body: str, ndev: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import sys
        sys.path.insert(0, "src")
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_moe_distributed_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe as M, transformer as T
        from repro.sharding import MeshRules
        cfg = dataclasses.replace(get_config("kimi_k2_1t").reduced(),
                                  capacity_factor=16.0, moe_sharding="ep")
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((2, 2), ("data", "model"))
        key = jax.random.PRNGKey(0)
        lp = jax.tree.map(lambda x: x[0], M.init_moe(cfg, key, 1))
        x = jax.random.normal(key, (4, 16, cfg.d_model))
        ref_out, ref_drop = M.moe_ffn(cfg, MeshRules(), lp, x)
        rules = MeshRules(mesh=mesh).with_moe("ep")
        with mesh:
            dist_out, dist_drop = jax.jit(
                lambda lp, x: M.moe_ffn(cfg, rules, lp, x))(lp, x)
        err = float(jnp.abs(ref_out - dist_out).max())
        print("ERR", err)
        assert err < 1e-4, err
    """)
    assert "ERR" in out


def test_hybrid_attention_distributed_matches_local():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.serve import hybrid_cache as H
        from repro.models.config import ModelConfig
        from repro.sharding import MeshRules
        L, B, Hkv, Hq, S, D = 1, 1, 2, 4, 1024, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        nb = S // H.BLOCK
        spec = H.HybridSpec(L, B, Hkv, D, nb, nb)
        k = jax.random.normal(ks[0], (L, B, Hkv, S, D))
        v = jax.random.normal(ks[1], (L, B, Hkv, S, D))
        cache = H.from_dense(spec, k, v, jnp.asarray([S - 37]), jnp.float32)
        q = jax.random.normal(ks[2], (B, Hq, D))
        cfg = ModelConfig("t", "dense", L, 64, Hq, Hkv, 128, 256, head_dim=D)
        lc = {kk: vv[0] for kk, vv in cache.items()
              if hasattr(vv, "ndim") and vv.ndim > 1
              and kk not in ("pos", "tail_len", "n_blocks")}
        lc.update({kk: cache[kk] for kk in ("n_blocks", "tail_len")})
        local = H.hybrid_attention(cfg, MeshRules(), lc, q, budget=nb)
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((2, 4), ("data", "model"))
        rules = MeshRules(mesh=mesh).with_kv_seq(("data", "model"))
        with mesh:
            dist = jax.jit(lambda lc, q: H.hybrid_attention(
                cfg, rules, lc, q, budget=nb))(lc, q)
        err = float(jnp.abs(local - dist).max())
        print("ERR", err)
        assert err < 1e-4, err
    """)
    assert "ERR" in out


def test_compressed_psum_across_pod_axis():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((4, 2), ("pod", "data"))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

        def f(xl):
            s, res = compressed_psum(xl[0], "pod")
            return s[None], res[None]

        with mesh:
            s, res = jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                   out_specs=P("pod"), check_vma=False)(x)
        true = jnp.sum(x, axis=0)
        err = float(jnp.abs(s[0] - true).max())
        scale = float(jnp.abs(x).max()) / 127.0
        print("ERR", err, "TOL", 4 * scale)
        assert err <= 4 * scale + 1e-6
    """)
    assert "ERR" in out


def test_train_step_runs_on_2x2_mesh():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_rules
        from repro.launch.steps import train_artifacts
        from repro.models.config import ShapeConfig
        cfg = get_config("qwen3_4b").reduced()
        shape = ShapeConfig("t", 32, 4, "train")
        from repro.launch.mesh import make_smoke_mesh
        mesh = make_smoke_mesh((2, 2), ("data", "model"))
        rules = make_rules(cfg, shape, mesh)
        step, args, in_sh, out_sh = train_artifacts(cfg, shape, rules,
                                                    n_micro=2)
        import numpy as np
        from repro.models import transformer as T
        from repro.optim import make_optimizer
        from repro.launch.steps import opt_config_for
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        init_opt, _ = make_optimizer(opt_config_for(cfg))
        opt = init_opt(params)
        batch = {"tokens": jnp.ones((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        with mesh:
            p2, o2, m = jax.jit(step, in_shardings=in_sh,
                                out_shardings=out_sh)(params, opt, batch)
        print("LOSS", float(m["loss"]))
        assert np.isfinite(float(m["loss"]))
    """)
    assert "LOSS" in out
