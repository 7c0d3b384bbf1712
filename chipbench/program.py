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

from chipbench import refmath


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def model_spec(cfg: dict):
    """The program's ``CTRModelSpec``: the six keys every configuration
    states, and each entry of its optional ``arch`` as a keyword (lists
    as tuples). A key the program does not know raises its ``TypeError``."""
    from repro.models.ctr import CTRModelSpec
    arch = {k: _tuples(v) for k, v in cfg.get("arch", {}).items()}
    return CTRModelSpec(name=cfg["name"],
                        field_sizes=tuple(cfg["schema"]["field_sizes"]),
                        embed_dim=cfg["embed_dim"],
                        hidden=tuple(cfg["hidden"]),
                        cross_layers=cfg.get("cross_layers", 0),
                        dtype=cfg["dtype"], **arch)


def _table_rows(cfg: dict, dim: int) -> int:
    """The program's height of a table of ``dim``-wide rows."""
    from repro.embedding import FusedEmbeddingSpec
    return FusedEmbeddingSpec(field_sizes=tuple(cfg["schema"]["field_sizes"]),
                              dim=dim, dtype=cfg["dtype"]).rows


def program_params(cfg: dict, ref_model, key: jax.Array) -> dict:
    """The program's parameter tree from the harness's weights: each
    embedding table (``ref_model.tables``: weight key -> row width) made
    at the program's table height (``refmath.table``'s ``rows``, the same
    rows then zeros), so no padded second copy of it is ever held, and
    packed into the program's lane-dense layout, a reshape of a table
    whole lines high; dense leaves pass through. Traceable."""
    from repro.kernels.multi_table_lookup import pack_rows

    def table(k, c, dim):
        return refmath.table(k, c, dim, rows=_table_rows(c, dim))
    out = ref_model.init_weights(cfg, key, table=table)
    for name in ref_model.tables(cfg):
        out[name] = {"mega_table": pack_rows(out[name])}
    return out


def make_params(cfg: dict, ref_model, key: jax.Array) -> dict:
    """The program's parameters, made on the device from ``key`` in one
    jitted call."""
    return jax.jit(lambda k: program_params(cfg, ref_model, k))(key)


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
