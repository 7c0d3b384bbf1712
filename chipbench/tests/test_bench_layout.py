"""What a configuration may state beyond one id per field: numeric and
multi-hot request rows, architecture keys for the program's spec, and a
table drawn block by block; and the references' counts for a pooled
gather. A configuration of that kind is added as files alone."""

import dataclasses
import json
import os
import shutil

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench import bench, check, loadgen, program, refmath, registry
from chipbench.tests.conftest import ROOT

ZIPF = {"dist": "zipf", "exponent": 1.1}


def _cfg(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,exponent", [
    ("dcnv2-criteo-d32-h1024", 1.1), ("dcnv2-criteo-d32-h1024", 0.0),
    ("deepfm-avazu-d16-h256", 1.1)])
def test_one_hot_rows_are_the_old_draw(name, exponent):
    """A schema without ``numeric`` or ``hotness``, or with one id a
    field, draws exactly the rows one zipf draw a field always gave."""
    schema = _cfg(name)["schema"]
    ids = {"dist": "zipf", "exponent": exponent}
    old = loadgen.zipf_ids(np.random.default_rng(2**33 + 5), 4096,
                           schema["field_sizes"], exponent)
    for s in (schema, {**schema, "hotness": [1] * len(schema["field_sizes"])}):
        got = loadgen.request_rows(np.random.default_rng(2**33 + 5), 4096,
                                   s, ids)
        assert got.dtype == np.int32 and np.array_equal(got, old)


def test_numeric_and_multi_hot_rows():
    schema = {"field_sizes": [50, 7, 100_000],
              "numeric": {"caps": [10, 1000, 5, 3], "exponent": 1.1},
              "hotness": [3, 1, 5]}
    rows = loadgen.request_rows(np.random.default_rng(9), 5000, schema, ZIPF)
    assert rows.dtype == np.int32 and rows.shape == (5000, 4 + 9)
    caps = np.array(schema["numeric"]["caps"])
    assert (rows[:, :4] >= 0).all() and (rows[:, :4] < caps).all()
    limits = np.repeat(schema["field_sizes"], schema["hotness"])
    assert (rows[:, 4:] >= 0).all() and (rows[:, 4:] < limits).all()
    # the slots of one field are drawn on their own, not copied
    big = rows[:, 8:]
    assert (big != big[:, :1]).any(axis=1).mean() > 0.5
    again = loadgen.request_rows(np.random.default_rng(9), 5000, schema,
                                 ZIPF)
    assert np.array_equal(rows, again)
    other = loadgen.request_rows(np.random.default_rng(10), 5000, schema,
                                 ZIPF)
    assert not np.array_equal(rows, other)
    with pytest.raises(ValueError):
        loadgen.request_rows(np.random.default_rng(9), 5,
                             {**schema, "hotness": [1, 1]}, ZIPF)


def test_split_rows_and_pooled_lookup_match_a_loop():
    cfg = {"schema": {"field_sizes": [6, 4, 9],
                      "numeric": {"caps": [5, 5], "exponent": 1.0},
                      "hotness": [2, 1, 3]}}
    rows = loadgen.request_rows(np.random.default_rng(4), 16, cfg["schema"],
                                ZIPF)
    numeric, ids = refmath.split_rows(cfg, rows)
    assert numeric.shape == (16, 2) and ids.shape == (16, 6)
    assert np.array_equal(np.concatenate([numeric, ids], axis=1), rows)
    tbl = np.random.default_rng(5).standard_normal((19, 4)).astype(
        np.float32)
    offsets = refmath.field_offsets(cfg)
    got = np.asarray(refmath.pooled_lookup(jnp.asarray(tbl), ids, offsets,
                                           cfg["schema"]["hotness"]))
    want = np.zeros((16, 3, 4), np.float32)
    for b in range(16):
        slot = 0
        for f, h in enumerate(cfg["schema"]["hotness"]):
            for _ in range(h):
                want[b, f] += tbl[offsets[f] + ids[b, slot]]
                slot += 1
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # one id a field: the plain lookup
    one = ids[:, [0, 2, 3]]
    np.testing.assert_array_equal(
        np.asarray(refmath.pooled_lookup(tbl, one, offsets, [1, 1, 1])),
        np.asarray(refmath.lookup(tbl, one, offsets)))


def test_gather_bytes_of_a_pooled_lookup():
    for k, dim, rb in ((39, 32, 4), (24, 1, 4), (26, 128, 1)):
        assert refmath.gather_bytes(512, k, dim, rb, hotness=[1] * k) \
            == refmath.gather_bytes(512, k, dim, rb) \
            == 512 * k * (2 * dim * rb + 4)
    # MLPerf's 26 fields, 214 ids a sample at d=128: rows and ids read,
    # 26 pooled rows written
    hot = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
           100, 27, 10, 3, 1, 1]
    assert sum(hot) == 214
    assert refmath.gather_bytes(1, 26, 128, hotness=hot) \
        == 214 * (128 * 4 + 4) + 26 * 128 * 4 == 123_736


def _table_cfg(block_rows=None):
    init = {"table_std": 0.05}
    if block_rows:
        init["block_rows"] = block_rows
    return {"schema": {"field_sizes": [4000, 1003]}, "init": init}


def test_blocked_table_rows_height_and_spread():
    cfg = _table_cfg(block_rows=512)
    key = jax.random.PRNGKey(2**31 + 11)
    t = np.asarray(refmath.table(key, cfg, 32))
    n, block = 5003, 512
    assert t.shape == (n, 32)
    assert np.array_equal(t, np.asarray(refmath.table(key, cfg, 32)))
    assert not np.array_equal(
        t, np.asarray(refmath.table(jax.random.PRNGKey(7), cfg, 32)))
    assert abs(t.mean()) < 0.02 * 0.05
    assert abs(t.std() / 0.05 - 1.0) < 0.02
    # block i is fold_in(key, i)'s draw; the last one clipped, not shifted
    whole = n // block

    def draw(i):
        return np.asarray(jax.random.normal(jax.random.fold_in(key, i),
                                            (block, 32)) * 0.05)
    # (to rounding: the loop's product may fuse otherwise than eager's)
    same = dict(rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(t[:block], draw(0), **same)
    np.testing.assert_allclose(t[block:2 * block], draw(1), **same)
    np.testing.assert_allclose(t[whole * block:],
                               draw(whole)[:n - whole * block], **same)
    assert not np.array_equal(t[:block], t[block:2 * block])
    # a taller array holds the same rows, then zeros
    tall = np.asarray(refmath.table(key, cfg, 32, rows=5008))
    assert tall.shape == (5008, 32)
    assert np.array_equal(tall[:n], t) and not tall[n:].any()


def test_unblocked_table_is_the_one_draw():
    cfg = _table_cfg()
    key = jax.random.PRNGKey(3)
    want = np.asarray(jax.random.normal(key, (5003, 16), jnp.float32)
                      * 0.05)
    assert np.array_equal(np.asarray(refmath.table(key, cfg, 16)), want)
    tall = np.asarray(refmath.table(key, cfg, 16, rows=5008))
    assert np.array_equal(tall[:5003], want) and not tall[5003:].any()


@pytest.mark.parametrize("block_rows", [None, 700])
def test_program_tables_hold_the_reference_rows(block_rows):
    """``make_params`` draws each table at the program's height; its
    packed rows are the reference's, then zeros."""
    from repro.kernels.multi_table_lookup import unpack_rows
    cfg = _cfg("deepfm-avazu-d16-h256")
    cfg["schema"]["field_sizes"] = [min(n, 301)
                                    for n in cfg["schema"]["field_sizes"]]
    cfg["hidden"] = [32, 32, 32]
    if block_rows:
        cfg["init"]["block_rows"] = block_rows
    ref_model = registry.Benchmark().model(cfg["model"])
    key = jax.random.PRNGKey(21)
    params = program.make_params(cfg, ref_model, key)
    ref = jax.jit(lambda k: ref_model.init_weights(cfg, k))(key)
    n = refmath.n_rows(cfg)
    for name, dim in ref_model.tables(cfg).items():
        rows = unpack_rows(params[name]["mega_table"], dim, np.float32)
        assert rows.shape[0] == program._table_rows(cfg, dim) > n
        assert np.array_equal(np.asarray(rows[:n]), np.asarray(ref[name]))
        assert not np.asarray(rows[n:]).any()


def test_model_spec_takes_arch_keys(monkeypatch):
    from repro.models import ctr
    cfg = _cfg("dcnv2-criteo-d32-h1024")
    plain = program.model_spec(cfg)
    assert plain == ctr.CTRModelSpec(
        name=cfg["name"], field_sizes=tuple(cfg["schema"]["field_sizes"]),
        embed_dim=32, hidden=(1024, 1024, 1024), cross_layers=3,
        dtype="float32")
    with pytest.raises(TypeError):
        program.model_spec({**cfg, "arch": {"no_such_key": 1}})

    @dataclasses.dataclass(frozen=True)
    class WiderSpec(ctr.CTRModelSpec):
        bottom: tuple = ()
        rank: int = 0
        hotness: tuple = ()
    monkeypatch.setattr(ctr, "CTRModelSpec", WiderSpec)
    spec = program.model_spec({**cfg, "arch": {
        "bottom": [512, 256, 128], "rank": 512, "hotness": [[1, 2], [3]]}})
    assert spec.bottom == (512, 256, 128) and spec.rank == 512
    assert spec.hotness == ((1, 2), (3,))
    assert spec.hidden == (1024, 1024, 1024) and spec.cross_layers == 3
    hash(spec)


_POOLED_MODEL = '''
"""A test model over numeric and multi-hot rows."""
import jax.numpy as jnp
from chipbench import refmath as rm


def tables(cfg):
    return {"emb": cfg["embed_dim"]}


def init_weights(cfg, key, table=rm.table):
    import jax
    k1, k2 = jax.random.split(key)
    m = len(cfg["schema"]["numeric"]["caps"])
    d_in = m + len(cfg["schema"]["field_sizes"]) * cfg["embed_dim"]
    return {"emb": table(k1, cfg, cfg["embed_dim"]),
            "head": rm.dense(k2, d_in, 1)}


def logits(cfg, w, rows):
    numeric, ids = rm.split_rows(cfg, rows)
    x = rm.pooled_lookup(w["emb"], ids, rm.field_offsets(cfg),
                         cfg["schema"]["hotness"])
    z = jnp.concatenate([jnp.log1p(numeric.astype(jnp.float32)),
                         x.reshape(x.shape[0], -1)], axis=1)
    return rm.linear(z, w["head"])[:, 0]


def flops_per_request(cfg):
    return 0


def gather_bytes(cfg, batch):
    return rm.gather_bytes(batch, len(cfg["schema"]["field_sizes"]),
                           cfg["embed_dim"],
                           hotness=cfg["schema"]["hotness"])
'''


def test_a_pooled_configuration_is_added_as_files(tmp_path):
    """A configuration with numeric features, multi-hot fields, an
    architecture key and a blocked table, its model and its mix, added as
    new files and ``BENCHMARK.json`` entries: the harness draws its rows,
    makes its weights and scores them with its reference."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    base = _cfg("dcnv2-criteo-d32-h1024")
    cfg = {**base, "name": "pooled-test", "model": "pooled_test",
           "embed_dim": 8, "arch": {"multi_hot_note": [1]},
           "schema": {"field_sizes": [1000, 30, 7],
                      "numeric": {"caps": [100, 10], "exponent": 1.1},
                      "hotness": [4, 1, 2]},
           "init": {**base["init"], "block_rows": 64}}
    (tmp_path / "chipbench" / "configs" / "pooled-test.json").write_text(
        json.dumps(cfg))
    (tmp_path / "chipbench" / "models" / "pooled_test.py").write_text(
        _POOLED_MODEL)
    (tmp_path / "chipbench" / "traffic" / "pooled-mix.json").write_text(
        json.dumps({"loop": "closed", "outstanding": 64, "pool": 512,
                    "max_rate_per_s": 1000, "ids": ZIPF}))
    spec["configs"].append({"name": "pooled-test", "source": "test",
                            "file": "chipbench/configs/pooled-test.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "pooled-test.closed",
                              "config": "pooled-test",
                              "traffic": "pooled-mix", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    b = registry.Benchmark(str(tmp_path))
    cell = b.workload("pooled-test.closed")
    c = b.config(cell["config"])
    ref_model = b.model(c["model"])
    streams = bench.seed_streams(2**32 + 1)
    rows, _, _ = bench.window_traffic(streams, b.mix(cell["traffic"]),
                                      c["schema"], 1.0)
    assert rows.shape == (512, 2 + 7)
    w = jax.jit(lambda k: ref_model.init_weights(c, k))(streams["key"])
    assert w["emb"].shape == (1037, 8)
    scores = check.reference_scores(c, ref_model, w, rows)
    assert np.isfinite(scores).all() and scores.std() > 1e-4
    assert ref_model.gather_bytes(c, 2) == 2 * (7 * (8 * 4 + 4)
                                                + 3 * 8 * 4)
    # the arch key reaches the program's spec, which refuses it
    with pytest.raises(TypeError):
        program.model_spec(c)
