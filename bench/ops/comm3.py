"""``mpi.icomm3``, NPB MG's ghost-layer exchange, on a copy of every
rank's drawn block, with the configuration's committed face datatypes."""
import numpy as np

from repro import mpi

FACES = ("axis1_face", "axis2_face", "axis3_face")
# a program without the operation is refused here, before any compile
icomm3 = mpi.icomm3


def post(comm, mix, ids, inputs):
    arrays = [np.array(a) for a in inputs]
    req = icomm3(comm, arrays, mix["dims"], [ids[f] for f in FACES])
    return [req], arrays


def outputs(posted):
    (req,), _ = posted
    return [np.array(o) for o in req.result or []]
