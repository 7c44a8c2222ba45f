"""The 95th percentile of request latency, in seconds, over every request
that arrived in the window: from its scheduled arrival to the end of the
service step that finalised it (the client's view; the time it waited to
be submitted counts).  Reads only the window's request records, so any
cell whose driver keeps them can report it.  A request left open when the
window ended counts with the window's end."""

import numpy as np


def read(ctx):
    recs = ctx["w"].get("records")
    if not recs:
        return None
    return float(np.percentile([done - arrived for arrived, done, _ in recs], 95))
