"""NPB MG ``comm3`` through the MPI layer, against a plain numpy reference.

``mpi.icomm3`` exchanges the ghost layer of one 3-D float64 block a rank on
a periodic 2×2×2 process grid, axis by axis, each face one typed message
(NPB 3.x ``mg.f``, ``comm3``/``give3``/``take3``).  Small faces travel
eager and are unpacked by the host; faces at or above the eager threshold
are rendezvous messages the receiving NIC unpacks into the ghost plane.
A rendezvous FIN reads back only the bytes its datatype names: a device
gather (``SpinNIC.read_named``) for a type with holes, the slot's slice for
a type that names its whole extent.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import mpi
from repro.core import ddt as ddtlib
from repro.core import spin_nic
from repro.net import LinkConfig

DIMS = (2, 2, 2)
D = ddtlib.MPI_DOUBLE


def face_types(n: int):
    """The axis-1, -2 and -3 faces of a ``u(n, n, n)`` block, as NPB's
    ``give3`` loops name them."""
    return (ddtlib.HVector(n - 2, 1, n * n * 8, ddtlib.Vector(n - 2, 1, n, D)),
            ddtlib.Vector(n - 2, n, n * n, D),
            ddtlib.Contiguous(n * n, D))


def npb_comm3(arrays, dims):
    """NPB ``comm3`` on copies of the ranks' C-order ``(n3, n2, n1)``
    blocks, rank ``c1 + d1·(c2 + d2·c3)``: per axis, ghost 0 takes the −1
    neighbour's plane n-2, ghost n-1 the +1 neighbour's plane 1, across the
    other axes' interiors for axes not yet exchanged."""
    u = [a.copy() for a in arrays]
    d1, d2, _ = dims
    coords = [(r % d1, r // d1 % d2, r // (d1 * d2)) for r in range(len(u))]

    def rank(c):
        return c[0] + d1 * (c[1] + d2 * c[2])

    for axis in range(3):
        old = [a.copy() for a in u]
        sel = [slice(None)] * 3
        for later in range(axis + 1, 3):
            sel[2 - later] = slice(1, -1)

        def plane(i):
            s = list(sel)
            s[2 - axis] = i
            return tuple(s)

        for r, c in enumerate(coords):
            lo, hi = list(c), list(c)
            lo[axis] = (c[axis] - 1) % dims[axis]
            hi[axis] = (c[axis] + 1) % dims[axis]
            u[r][plane(0)] = old[rank(lo)][plane(-2)]
            u[r][plane(-1)] = old[rank(hi)][plane(1)]
    return u


def _world(n: int, loss: float, eager_threshold: int = 4096, seed: int = 3):
    reg = mpi.DatatypeRegistry()
    faces = [reg.register(t, name=f"face{a + 1}")
             for a, t in enumerate(face_types(n))]
    comm = mpi.Communicator(
        8, registry=reg, seed=seed,
        link_cfg=LinkConfig(loss=loss, latency=1),
        cfg=mpi.MpiConfig(eager_threshold=eager_threshold, n_rdv_slots=8,
                          mtu_payload=1408, slmp_window=64, batch=32))
    return comm, faces


def _blocks(n: int, seed: int):
    return list(np.random.default_rng(seed).random((8, n, n, n)))


@pytest.mark.parametrize("loss", (0.0, 0.05), ids=("loss0", "loss5"))
@pytest.mark.parametrize("n,threshold,path", [
    (6, 4096, "eager"), (18, 1024, "rendezvous")])
def test_icomm3_matches_the_npb_reference(n, threshold, path, loss):
    comm, faces = _world(n, loss, eager_threshold=threshold)
    inputs = _blocks(n, seed=int(n + 100 * loss))
    arrays = [a.copy() for a in inputs]
    req = mpi.icomm3(comm, arrays, DIMS, faces)
    comm.wait(req, max_ticks=20_000)
    want = npb_comm3(inputs, DIMS)
    for got, ref, out in zip(arrays, want, req.result):
        assert out is got                      # exchanged in place
        np.testing.assert_array_equal(got.view(np.uint8), ref.view(np.uint8))
    sent = comm.stats()
    face_bytes = sum(comm.registry.msg_bytes(f) for f in faces)
    assert (req.rounds, req.msgs_total) == (3, 8 * 12)
    assert req.bytes_wire == 8 * 2 * face_bytes
    eager = sum(s["eager_sent"] for s in sent)
    rdv = sum(s["rdv_sent"] for s in sent)
    # every face is one typed message, never cut into segments
    assert (eager, rdv) == ((48, 0) if path == "eager" else (0, 48))


def test_face_typemaps_are_npb_slices():
    """At each face's displacement its typemap is, in order, the bytes of
    NPB's ``give3`` slice of ``u(n1, n2, n3)``."""
    n = 18
    reg = mpi.DatatypeRegistry()
    faces = [reg.register(t) for t in face_types(n)]
    elems = np.arange(n ** 3).reshape(n, n, n)          # (i3, i2, i1)
    inner = slice(1, -1)
    slices = {0: lambda i: elems[inner, inner, i],
              1: lambda i: elems[inner, i, :],
              2: lambda i: elems[i, :, :]}
    strides = (8, 8 * n, 8 * n * n)
    for axis, face in enumerate(faces):
        c = reg.committed(face)
        for i in (0, 1, n - 2, n - 1):
            disp = i * strides[axis] + sum(strides[b]
                                           for b in range(axis + 1, 3))
            want = (slices[axis](i).reshape(-1)[:, None] * 8
                    + np.arange(8)).reshape(-1)
            np.testing.assert_array_equal(c.msg_to_mem + disp, want)
    assert [reg.msg_bytes(f) for f in faces] == [2048, 2304, 2592]


@pytest.mark.parametrize("dims", [(1, 2, 4), (2, 4, 1), (8, 1, 1)])
def test_a_grid_axis_under_two_is_refused(dims):
    comm, faces = _world(6, 0.0)
    with pytest.raises(ValueError, match="at least 2 ranks on every axis"):
        mpi.icomm3(comm, _blocks(6, 0), dims, faces)


def test_checkpoint_mid_comm3_roundtrips():
    """A checkpoint mid-exchange restores into a fresh communicator, whose
    plan finishes on its own copies of the arrays in the same ticks with
    the same bytes as the run that went on."""
    n = 18
    inputs = _blocks(n, seed=9)
    want = npb_comm3(inputs, DIMS)
    c1, faces = _world(n, 0.05, eager_threshold=1024)
    arrays = [a.copy() for a in inputs]
    h1 = mpi.icomm3(c1, arrays, DIMS, faces)
    c1.progress(30)
    plan = next(iter(c1._plans.values()))
    assert not h1.done and min(plan.axis) == 0 < max(plan.axis), \
        "the checkpoint must land with ranks on different axes"
    snap = c1.checkpoint()
    c1.wait(h1, max_ticks=20_000)
    end1 = c1.now
    for got, ref in zip(h1.result, want):
        np.testing.assert_array_equal(got, ref)

    c2, _ = _world(n, 0.05, eager_threshold=1024)
    h2 = next(iter(c2.restore(snap).values()))
    c2.wait(h2, max_ticks=20_000)
    assert c2.now == end1
    for got, ref, mine in zip(h2.result, want, arrays):
        assert got is not mine
        np.testing.assert_array_equal(got, ref)


# ------------------------------------------------ FIN reads of named bytes
def _types_with_holes():
    return dict(zip(("face1", "face2", "face3"), face_types(18)),
                complex=(ddtlib.complex_ddt(), 512))


def test_named_read_equals_the_masked_full_extent_read():
    """Over a window of random bytes, every slot: the gather of the named
    offsets lands what the full-extent read through the mask landed."""
    reg = mpi.DatatypeRegistry()
    ids = {}
    for name, t in _types_with_holes().items():
        ddt, count = t if isinstance(t, tuple) else (t, 1)
        ids[name] = reg.register(ddt, count=count, name=name)
    comm = mpi.Communicator(2, registry=reg, cfg=mpi.MpiConfig(n_rdv_slots=2))
    node, p = comm.nodes[1], comm.params
    rng = np.random.default_rng(1)
    node.state = dataclasses.replace(node.state, host=jnp.asarray(
        rng.integers(0, 256, comm.nic.host_bytes, dtype=np.uint8)))
    assert reg.dense(ids["face3"]) and ids["face3"] not in comm.named
    for name, i in ids.items():
        if reg.dense(i):
            continue
        mask, mem = reg.mem_mask(i), reg.mem_bytes(i)
        for slot in range(p.n_rdv_slots):
            base = p.rdv_base + slot * p.rdv_region_bytes
            old = np.zeros(mem, np.uint8)
            old[mask] = node.read_host(base, mem)[mask]
            new = np.zeros(mem, np.uint8)
            new[reg.named(i)] = node.read_named(base, comm.named[i])
            np.testing.assert_array_equal(new, old, err_msg=name)
    assert reg.named(ids["complex"]).size < reg.mem_bytes(ids["complex"])


@pytest.mark.parametrize("name,gathers", [("face3", 0), ("face1", 1),
                                          ("complex", 1)])
def test_only_a_type_with_holes_launches_the_gather(monkeypatch, name,
                                                    gathers):
    t = _types_with_holes()[name]
    ddt, count = t if isinstance(t, tuple) else (t, 1)
    reg = mpi.DatatypeRegistry()
    i = reg.register(ddt, count=count)
    comm = mpi.Communicator(2, registry=reg, link_cfg=LinkConfig(latency=1),
                            cfg=mpi.MpiConfig(eager_threshold=1024))
    launched = []
    read_named = spin_nic._read_named
    monkeypatch.setattr(spin_nic, "_read_named", lambda *a: (
        launched.append(1), read_named(*a))[1])
    mem = np.random.default_rng(2).integers(0, 256, reg.mem_bytes(i),
                                            dtype=np.uint8)
    buf = np.zeros_like(mem)
    r = comm.irecv(1, buf, source=0, tag=4)
    s = comm.isend(0, 1, mem, tag=4, datatype=i)
    comm.waitall([r, s])
    assert comm.stats()[0]["rdv_sent"] == 1 and len(launched) == gathers
    c = reg.committed(i)
    np.testing.assert_array_equal(
        buf, ddtlib.unpack_np(c, ddtlib.pack_np(c, mem), np.zeros_like(mem)))
