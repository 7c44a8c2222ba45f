"""Requests completed in the window, per second of the window: the
request records whose status is ``completed``, over the window's length.
Requests still queued or in flight when the window ends are neither
completed nor failed.  Reads only the window's request records."""


def read(ctx):
    w = ctx["w"]
    if w.get("records") is None or w["window_s"] <= 0:
        return None
    return sum(1 for _, _, status in w["records"] if status == "completed") \
        / w["window_s"]
