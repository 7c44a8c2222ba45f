"""The fleet ChemCache's hit rate in the training window: hits over
lookups (``chem_stats``), in percent."""


def read(ctx):
    c = ctx["delta"]["chem"]
    if ctx["driver"] != "train" or not c["lookups"]:
        return None
    return 100.0 * c["hits"] / c["lookups"]
