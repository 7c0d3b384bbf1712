"""Multi-device test bodies (run in a subprocess with 8 host devices)."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import numpy as np
import jax
import jax.numpy as jnp


def sharded_lookup():
    """Vocab-parallel fused lookup == replicated lookup."""
    from repro.core import FusedEmbeddingCollection, FusedEmbeddingSpec
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(2, 4)
    spec = FusedEmbeddingSpec(field_sizes=(7, 30, 3, 12), dim=8,
                              pad_rows_to=4)
    emb = FusedEmbeddingCollection(spec)
    params = emb.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(np.stack([rng.integers(0, n, size=16)
                                for n in spec.field_sizes], axis=1),
                      dtype=jnp.int32)
    want = emb.apply(params, ids, strategy="jnp")
    offsets = jnp.asarray(spec.offsets)
    with mesh:
        got = jax.jit(lambda p, i: emb.store.lookup_sharded(
            p, i, offsets, mesh))(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def compressed_psum():
    from repro.launch.mesh import make_test_mesh
    from repro.training.compression import make_compressed_dp_step
    mesh = make_test_mesh(4, 2)

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4))}
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
             "y": jax.random.normal(jax.random.PRNGKey(2), (16, 4))}
    step = jax.jit(make_compressed_dp_step(loss_fn, mesh))
    with mesh:
        loss_c, grads_c = step(params, batch)
    loss_e, grads_e = jax.value_and_grad(loss_fn)(params, batch)
    assert abs(float(loss_c) - float(loss_e)) < 1e-5
    rel = (np.abs(np.asarray(grads_c["w"]) - np.asarray(grads_e["w"])).max()
           / np.abs(np.asarray(grads_e["w"])).max())
    assert rel < 0.02, rel


def flash_decode():
    """Distributed flash-decode == single-device decode."""
    from repro.launch.mesh import make_test_mesh
    from repro.models.lm import layers as L
    from repro.models.lm.config import LMConfig
    from repro.models.lm.transformer import DenseTransformer
    mesh = make_test_mesh(2, 4)
    cfg = LMConfig(name="t", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                   dtype="float32", remat=False)
    m = DenseTransformer(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 128)
    cache = m.init_cache(4, 16)
    lp, cache = m.prefill(params, toks, cache)
    nxt = jnp.argmax(lp, -1)[:, None].astype(toks.dtype)
    ref, _ = m.decode_step(params, nxt, cache)
    m.decode_ctx = L.DecodeShardCtx(mesh=mesh, batch_axes="data",
                                    seq_axis="model")
    with mesh:
        got, _ = jax.jit(m.decode_step)(params, nxt, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def param_specs():
    """Every assigned arch gets a complete, divisibility-fitted spec tree."""
    from repro.configs import ARCH_NAMES, get_config
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_test_mesh
    from repro.models.lm import make_lm_model
    mesh = make_test_mesh(2, 4)
    for arch in ARCH_NAMES:
        cfg = get_config(arch).reduced()
        model = make_lm_model(cfg)
        shapes = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0)))
        specs = shd.fit_spec_tree(
            mesh, shd.param_specs(cfg.family, shapes, cfg), shapes)
        n_sharded = sum(
            1 for s in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))
            if any(a is not None for a in s))
        assert n_sharded > 0, arch


def cell_lowering():
    """A reduced cell lowers + compiles on the test mesh for all 3 kinds."""
    import dataclasses
    import repro.configs as C
    import repro.configs.qwen3_4b as mod
    mod.CONFIG = mod.CONFIG.reduced(qk_norm=True)
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import build_cell
    mesh = make_test_mesh(2, 4)
    for shape, kind in [("train_4k", "train"), ("prefill_32k", "prefill"),
                        ("decode_32k", "decode")]:
        C.SHAPES[shape] = C.ShapeCell(shape, 64, 8, kind)
        cell = build_cell("qwen3-4b", shape, mesh)
        compiled = cell.lower()[0].compile()
        assert compiled.cost_analysis()["flops"] > 0


if __name__ == "__main__":
    case = sys.argv[1]
    globals()[case]()
    print(f"{case} OK")
