"""95th percentile of all gaps between successive tokens of one request
that both landed in the window."""
import numpy as np


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or len(rec["itl_s"]) < 20:
        return None
    return 1e3 * float(np.percentile(np.asarray(rec["itl_s"]), 95.0))
