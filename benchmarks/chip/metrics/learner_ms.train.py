"""Host milliseconds per learner update: the ``bench.run_updates`` span
(replay sampling, the update dispatches and the loss read-back) over the
updates made in the window."""


def read(ctx):
    d = ctx["delta"]
    if ctx["driver"] != "train" or not d["updates"]:
        return None
    return 1e3 * d["spans"]["total"].get("bench.run_updates", 0.0) / d["updates"]
