"""Host chemistry's share of the training window: seconds the rollout
engine spent enumerating candidates and fingerprinting them (its own
``chem_stats`` counters), over the window's seconds, in percent."""


def read(ctx):
    if ctx["driver"] != "train":
        return None
    return 100.0 * ctx["delta"]["chem"]["chem_s"] / ctx["window_s"]
