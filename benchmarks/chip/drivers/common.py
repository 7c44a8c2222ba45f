"""What both drivers share: weights from the seed, the spanned property
service, predictor shape warm-up, and the capture of what the timed path
produced for the comparison with the reference."""

from __future__ import annotations

from .. import harness


def predictor_models(cfg: dict):
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS

    p = cfg["predictors"]
    return (AlfabetS(hidden=p["bde_hidden"], rounds=p["bde_rounds"]),
            AIMNetS(hidden=p["ip_hidden"], n_ensemble=p["ip_ensemble"]))


def weight_keys(pseed: int):
    """The keys every weight is drawn from: (Q, BDE, IP).  The reference
    derives the same keys from the same program seed."""
    import jax

    base = jax.random.PRNGKey(pseed)
    return tuple(jax.random.fold_in(base, i) for i in (0, 1, 2))


def make_weights(cfg: dict, pseed: int):
    """The two predictors' weights, drawn from the seed on the device in
    one jitted call (the trainer draws the Q-networks itself)."""
    import jax

    from repro.core.agent import QNetwork

    bde_m, ip_m = predictor_models(cfg)
    net = QNetwork(hidden=tuple(cfg["qnet"]["hidden"]))
    _, kb, ki = weight_keys(pseed)

    def build(kb, ki):
        return {"bde": bde_m.init(kb), "ip": ip_m.init(ki)}

    w = jax.jit(build)(kb, ki)
    jax.block_until_ready(w)
    return net, bde_m, ip_m, w


class SpannedService:
    """The property service as the trainer sees it: ``predict`` runs
    inside the ``bench.predict`` span, and while ``record`` is a list each
    call's molecules and answers are appended to it.  Every other
    attribute is the inner service's."""

    def __init__(self, inner, spans: harness.Spans):
        self.inner = inner
        self._spans = spans
        self.record: list | None = None

    def predict(self, mols):
        with self._spans.span("bench.predict"):
            out = self.inner.predict(mols)
        if self.record is not None:
            self.record.append((list(mols), list(out)))
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def warm_predictor_shapes(service, pool) -> int:
    """Compile every padded batch shape of the predictors' ladder by
    predicting that many distinct molecules, then give the service a
    fresh, empty answer cache, so the window starts as a fresh service
    would.  Returns the number of shapes."""
    from repro.predictors.cache import LRUCache
    from repro.predictors.service import capacity_table

    capacity = service.cache.capacity if service.cache is not None else 200_000
    seen, distinct = set(), []
    for m in pool:
        if m.iso_key() not in seen and m.num_atoms <= service.max_atoms:
            seen.add(m.iso_key())
            distinct.append(m)
    rungs = capacity_table(service.max_batch_hint)
    if len(distinct) < rungs[-1]:
        raise harness.BenchError(f"{len(distinct)} warm molecules for a "
                                 f"predictor batch of {rungs[-1]}")
    for k in rungs:
        service.cache = None
        service.predict(distinct[:k])
    service.cache = LRUCache(capacity)
    return len(rungs)


def chem_counters(engine) -> dict:
    st = engine.chem_stats()
    return {"chem_s": st["enum_s"] + st["fp_s"], "enum_s": st["enum_s"],
            "fp_s": st["fp_s"], "hits": st.get("hits", 0),
            "lookups": st.get("lookups", 0), "env_steps": st["env_steps"]}
