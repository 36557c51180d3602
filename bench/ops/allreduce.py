"""``mpi.iallreduce`` of one vector a rank, with the mix's ``algorithm``."""
import numpy as np

from repro import mpi


def post(comm, mix, ids, inputs):
    req = mpi.iallreduce(comm, [v.copy() for v in inputs],
                         algorithm=mix["algorithm"])
    return [req], None


def outputs(posted):
    (req,), _ = posted
    return [np.array(o) for o in req.result or []]
