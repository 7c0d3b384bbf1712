"""The system under test, built from a configuration file.

This is the only module of the harness that imports the program
(``src/repro``). It turns a configuration and the harness's own weights
into a ``ServingRuntime`` hosting one engine, exactly as a deployment
would: a ``CTRModel`` at the configured widths, a ``CachedStore`` (or
whatever ``store.kind`` names) over the full table, a ``TimeoutBatch``
over the bucket ladder, and the shared device scheduler.

Weights come from the harness (``chipbench/models/<model>.py``), in its
logical layout; :func:`program_params` maps them onto the program's
parameter tree. The reference never sees anything built here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def model_spec(cfg: dict):
    from repro.models.ctr import CTRModelSpec
    return CTRModelSpec(name=cfg["name"],
                        field_sizes=tuple(cfg["schema"]["field_sizes"]),
                        embed_dim=cfg["embed_dim"],
                        hidden=tuple(cfg["hidden"]),
                        cross_layers=cfg.get("cross_layers", 0),
                        dtype=cfg["dtype"])


def program_params(cfg: dict, tables: dict, weights: dict) -> dict:
    """The program's parameter tree from the harness's weights: every
    embedding table (``tables``: weight key -> row width) zero-padded to
    the program's table height and packed into its lane-dense layout;
    dense leaves pass through. Traceable, so the harness makes the
    program's parameters on the device in one jitted call."""
    from repro.embedding import FusedEmbeddingSpec
    from repro.kernels.multi_table_lookup import pack_rows
    sizes = tuple(cfg["schema"]["field_sizes"])
    out = dict(weights)
    for key, dim in tables.items():
        spec = FusedEmbeddingSpec(field_sizes=sizes, dim=dim,
                                  dtype=cfg["dtype"])
        t = weights[key]
        t = jnp.pad(t, ((0, spec.rows - t.shape[0]), (0, 0)))
        out[key] = {"mega_table": pack_rows(t)}
    return out


def make_params(cfg: dict, ref_model, key: jax.Array) -> dict:
    """The program's parameters, made on the device from ``key`` in one
    jitted call."""
    def init(k):
        return program_params(cfg, ref_model.tables(cfg),
                              ref_model.init_weights(cfg, k))
    return jax.jit(init)(key)


def _store(cfg: dict):
    from repro.embedding import CachedStore, HostBackedStore
    spec = model_spec(cfg).embedding_spec()
    st = cfg["store"]
    row_dtype = None if st["row_dtype"] == cfg["dtype"] else st["row_dtype"]
    if st["kind"] == "cached":
        return CachedStore(spec, capacity=st["capacity"],
                           row_dtype=row_dtype)
    if st["kind"] == "host":
        return HostBackedStore(spec, capacity=st["capacity"],
                               staging_capacity=st["staging_capacity"],
                               row_dtype=row_dtype)
    if st["kind"] == "dense":
        return None
    raise ValueError(f"unknown store kind {st['kind']!r}")


def _policy(cfg: dict):
    from repro.serving import BucketedBatch, TimeoutBatch
    b = cfg["batching"]
    inner = BucketedBatch(tuple(b["buckets"]))
    if b["policy"] == "timeout":
        return TimeoutBatch(inner, max_wait_ms=b["max_wait_ms"])
    if b["policy"] == "bucketed":
        return inner
    raise ValueError(f"unknown batching policy {b['policy']!r}")


class Deployment:
    """One configuration served through ``ServingRuntime``.

    ``submit(row)`` is the timed path's entry; ``stats()`` a consistent
    snapshot of the engine's counters; ``admit()`` re-admits the store's
    cache from the traffic observed so far (the one refresh a run makes).
    """

    def __init__(self, cfg: dict, params: dict):
        from repro.models.ctr import CTR_MODELS
        from repro.serving import ServingRuntime
        self.cfg = cfg
        self.name = cfg["name"]
        rt_cfg = cfg["runtime"]
        self.runtime = ServingRuntime(scheduler=rt_cfg["scheduler"],
                                      pool_size=rt_cfg["pool_size"],
                                      refresh_every=cfg["store"].get(
                                          "refresh_every"))
        model = CTR_MODELS[cfg["model"]](model_spec(cfg))
        self.engine = self.runtime.add_model(
            self.name, model, params, policy=_policy(cfg),
            store=_store(cfg), compute_dtype=cfg["compute_dtype"])

    @property
    def buckets(self) -> tuple[int, ...]:
        return tuple(self.engine.policy.buckets)

    def warm_plans(self) -> None:
        self.runtime.warmup()

    def start(self) -> None:
        self.runtime.start()

    def submit(self, row):
        return self.runtime.submit(self.name, row)

    def admit(self) -> None:
        self.runtime.refresh_all()

    def stats(self):
        return self.engine.stats.snapshot()

    def stop(self) -> None:
        self.runtime.stop()
