"""The reference of ``comm3``: NPB MG's ghost-layer exchange by slicing.

Each rank holds a C-order ``(n, n, n)`` block of uniform float64 values,
i1 fastest as in ``mg.f``'s ``u(n1, n2, n3)``; rank ``c1 + d1·(c2 + d2·c3)``
sits at ``(c1, c2, c3)`` of the periodic process grid ``dims``.  Axis by
axis, 1 then 2 then 3, ghost plane 0 takes the −1 neighbour's plane n-2
and ghost plane n-1 the +1 neighbour's plane 1, across the interior of the
axes not yet exchanged and the whole of those exchanged before: edges and
corners arrive through the later axes.  Every other byte is unchanged.

The number compared is ``bytes_wrong``, the bytes of all ranks' arrays that
differ from the reference's; it is exact, so its limit is 0.  The control
runs the reference on the inputs rounded to float32, one step below the
stated float64.
"""
import numpy as np

INNER = slice(1, -1)


def draw(mix, config, rng):
    n = mix["n"]
    return rng.random((config["ranks"], n, n, n))


def _comm3(u, dims):
    """The exchange in place on the stacked blocks ``u`` (rank, i3, i2,
    i1), seen as a grid (c3, c2, c1, i3, i2, i1)."""
    d1, d2, d3 = dims
    g = u.reshape((d3, d2, d1) + u.shape[1:])
    for axis in range(3):
        plane_axis, grid_axis = 5 - axis, 2 - axis
        sel = [slice(None)] * 6
        for later in range(axis + 1, 3):
            sel[5 - later] = INNER

        def plane(i):
            s = list(sel)
            s[plane_axis] = i
            return tuple(s)
        from_below = np.roll(g[plane(-2)], 1, axis=grid_axis)
        from_above = np.roll(g[plane(1)], -1, axis=grid_axis)
        g[plane(0)] = from_below
        g[plane(-1)] = from_above
    return u


def expected(mix, config, inputs):
    return _comm3(np.array(inputs, np.float64), mix["dims"])


def compare(mix, config, inputs, outputs):
    want = expected(mix, config, inputs)
    if len(outputs) != len(want) or any(
            np.shape(o) != w.shape for o, w in zip(outputs, want)):
        return {"bytes_wrong": float(want.nbytes)}
    wrong = sum(np.count_nonzero(
        np.asarray(o, np.float64).view(np.uint8) != w.view(np.uint8))
        for o, w in zip(outputs, want))
    return {"bytes_wrong": float(wrong)}


def control(mix, config, inputs):
    low = np.asarray(inputs).astype(np.float32).astype(np.float64)
    return expected(mix, config, low)


def faces(n):
    """The configuration's face datatypes of a ``u(n, n, n)`` block, as
    ``give3`` loops over them: axis 1 ``u(i1, 2:n-1, 2:n-1)``, axis 2
    ``u(1:n, i2, 2:n-1)``, axis 3 ``u(1:n, 1:n, i3)``."""
    return [
        {"name": "axis1_face", "count": 1, "type": {"hvector": {
            "count": n - 2, "blocklen": 1, "stride_bytes": n * n * 8,
            "base": {"vector": {"count": n - 2, "blocklen": 1, "stride": n,
                                "base": "float64"}}}}},
        {"name": "axis2_face", "count": 1, "type": {"vector": {
            "count": n - 2, "blocklen": n, "stride": n * n,
            "base": "float64"}}},
        {"name": "axis3_face", "count": 1, "type": {"contiguous": {
            "count": n * n, "base": "float64"}}},
    ]


def small(mix, config):
    """Blocks of 18³, the process grid and ranks left whole; the eager
    threshold below the smallest face (2,048 B), so the faces still travel
    as rendezvous messages the NIC unpacks, as at the timed size."""
    n = 18
    return (dict(mix, n=n),
            dict(config, datatypes=faces(n),
                 mpi=dict(config["mpi"], eager_threshold=1024)))
