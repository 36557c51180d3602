"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import packet as pkt
from repro.kernels.checksum import ops as cops
from repro.kernels.checksum.ref import checksum_ref
from repro.kernels.ddt import ops as dops
from repro.kernels.ddt.ref import ddt_gather_ref
from repro.kernels.matcher import ops as mops
from repro.kernels.matcher.ref import match_ref


# ------------------------------------------------------------- ddt gather
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint8,
                                   np.uint32, "bfloat16"])
@pytest.mark.parametrize("s,i", [(16, 16), (100, 777), (1000, 333),
                                 (513, 1025), (2048, 64)])
def test_ddt_gather_matches_ref(dtype, s, i):
    rng = np.random.default_rng(hash((s, i)) % 2**31)
    if dtype == "bfloat16":
        src = jnp.asarray(rng.normal(size=s).astype(np.float32),
                          jnp.bfloat16)
    elif np.issubdtype(np.dtype(dtype), np.floating):
        src = jnp.asarray(rng.normal(size=s).astype(dtype))
    else:
        src = jnp.asarray(rng.integers(0, 200, size=s).astype(dtype))
    idx = jnp.asarray(rng.integers(-1, s, size=i).astype(np.int32))
    out_k = dops.gather(src, idx, use_kernel=True)
    out_r = ddt_gather_ref(src, idx)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_ddt_gather_fill_value():
    src = jnp.arange(8, dtype=jnp.float32)
    idx = jnp.asarray([-1, 3, -1, 7], jnp.int32)
    out = dops.gather(src, idx, fill=0, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(out), [0, 3, 0, 7])


def test_ddt_pack_unpack_roundtrip_kernel():
    from repro.core import ddt as ddtlib
    c = ddtlib.commit(ddtlib.simple_ddt(), count=3)
    pack_idx, unpack_idx = ddtlib.element_maps(c, 4)
    rng = np.random.default_rng(0)
    mem = jnp.asarray(rng.normal(size=c.mem_bytes // 4).astype(np.float32))
    msg = dops.pack(mem, jnp.asarray(pack_idx), use_kernel=True)
    dst = jnp.zeros_like(mem)
    out = dops.unpack(msg, jnp.asarray(unpack_idx), dst, use_kernel=True)
    # every mapped position must round-trip
    mask = unpack_idx >= 0
    np.testing.assert_allclose(np.asarray(out)[mask],
                               np.asarray(mem)[mask], rtol=0)


# -------------------------------------------------------------- checksum
@pytest.mark.parametrize("n_pkts", [1, 5, 130])
def test_checksum_kernel_vs_ref_and_numpy(n_pkts):
    rng = np.random.default_rng(n_pkts)
    frames = [pkt.make_icmp_echo(
        rng.integers(0, 256, size=int(rng.integers(0, 900))).astype(
            np.uint8))
        for _ in range(n_pkts)]
    b = pkt.stack_frames(frames)
    k = cops.internet_checksum(b.data, b.length, start=pkt.L4_BASE,
                               use_kernel=True)
    r = checksum_ref(b.data, b.length, pkt.L4_BASE)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    # frames carry a correct embedded checksum => total checksum == 0
    np.testing.assert_array_equal(np.asarray(k), np.zeros(n_pkts))


def test_checksum_against_numpy_oracle_random_payloads():
    rng = np.random.default_rng(7)
    frames = []
    expected = []
    for ln in (0, 1, 2, 63, 64, 500):
        payload = rng.integers(0, 256, size=ln).astype(np.uint8)
        f = pkt.make_udp(payload)
        frames.append(f)
        expected.append(pkt.internet_checksum_np(f[pkt.L4_BASE:]))
    b = pkt.stack_frames(frames)
    k = cops.internet_checksum(b.data, b.length, start=pkt.L4_BASE,
                               use_kernel=True)
    np.testing.assert_array_equal(np.asarray(k), expected)


# --------------------------------------------------------------- matcher
def _tables():
    from repro.core import matching as m
    return m.MatchTables.build([m.ruleset_icmp_echo(),
                                m.ruleset_udp_pingpong(9999),
                                m.ruleset_slmp(9330)])


@pytest.mark.parametrize("n", [1, 7, 128, 200])
def test_matcher_kernel_vs_ref(n):
    rng = np.random.default_rng(n)
    frames = []
    for i in range(n):
        kind = i % 4
        payload = rng.integers(0, 256, size=32).astype(np.uint8)
        if kind == 0:
            frames.append(pkt.make_icmp_echo(payload))
        elif kind == 1:
            frames.append(pkt.make_udp(payload, dport=9999))
        elif kind == 2:
            frames.append(pkt.make_slmp(i, 0, pkt.SLMP_FLAG_EOM, payload))
        else:
            frames.append(pkt.make_udp(payload, dport=1234))  # no match
    b = pkt.stack_frames(frames)
    t = _tables()
    words = b.words()
    mk, ek = mops.match(words, t.rules, t.modes, use_kernel=True)
    mr, er = match_ref(words, t.rules, t.modes)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))


def test_matcher_or_mode():
    from repro.core import matching as m
    rs = m.Ruleset(mode=m.MODE_OR,
                   rules=[m.RULE_IP_PROTO(pkt.IPPROTO_ICMP),
                          m.RULE_IP_PROTO(pkt.IPPROTO_UDP),
                          m.RULE_FALSE()],
                   eom=m.RULE_FALSE())
    t = m.MatchTables.build([rs])
    frames = [pkt.make_icmp_echo(np.zeros(8, np.uint8)),
              pkt.make_udp(np.zeros(8, np.uint8))]
    b = pkt.stack_frames(frames)
    for uk in (False, True):
        mm, _ = mops.match(b.words(), t.rules, t.modes, use_kernel=uk)
        assert bool(mm[0, 0]) and bool(mm[1, 0])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matcher_kernel_full_u32_range(seed):
    """Words, masks and ranges with the top bit set (the kernel compares
    unsigned values through int32), word indices past the packet (clipped
    like the reference), and both combiner modes."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 1 << 32, size=(130, pkt.WORDS), dtype=np.uint64)
    rules = rng.integers(0, 1 << 32, size=(3, 4, 4), dtype=np.uint64)
    rules[:, :, 0] = rng.integers(0, pkt.WORDS + 8, size=(3, 4))
    # half the ranges bracket the masked word so matches actually happen
    w_sel = words[0, np.minimum(rules[:, :, 0], pkt.WORDS - 1)]
    v = w_sel & rules[:, :, 1]
    near = rng.random((3, 4)) < 0.5
    near[0] = True                  # context 0 matches packet 0
    rules[:, :, 2] = np.where(near, v // 2, rules[:, :, 2])
    rules[:, :, 3] = np.where(near, v + (((1 << 32) - 1 - v) // 2),
                              rules[:, :, 3])
    words = jnp.asarray(words.astype(np.uint32))
    rules = jnp.asarray(rules.astype(np.uint32))
    modes = jnp.asarray(rng.integers(0, 2, size=3).astype(np.int32))
    mk, ek = mops.match(words, rules, modes, use_kernel=True)
    mr, er = match_ref(words, rules, modes)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))
    assert bool(mk[0, 0]) and bool(ek[0, 0])


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("shape", [
    (2, 64, 64, 4, 2, 32, True, 0),      # causal GQA
    (1, 96, 96, 2, 1, 16, True, 32),     # causal + sliding window (MQA)
    (2, 48, 96, 4, 4, 32, False, 0),     # bidirectional (encoder/cross)
    (1, 32, 32, 2, 2, 64, True, 0),      # head_dim 64
])
def test_flash_attention_kernel_vs_refs(shape):
    from repro.kernels.flash_attention import ops as fops
    from repro.models import attention as A
    b, sq, sk, h, kv, d, causal, window = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, sk, kv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, sk, kv, d)), jnp.bfloat16)
    out_k = fops.flash_attention(q, k, v, causal=causal, window=window,
                                 use_kernel=True, block_q=32, block_k=32)
    out_r = fops.flash_attention(q, k, v, causal=causal, window=window,
                                 use_kernel=False)
    out_b = A.blockwise_attention(q, k, v, causal=causal, window=window,
                                  block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=0.06)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_b, np.float32),
        atol=0.06)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_dtype_sweep(dtype):
    from repro.kernels.flash_attention import ops as fops
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), dt)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), dt)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), dt)
    out_k = fops.flash_attention(q, k, v, use_kernel=True,
                                 block_q=32, block_k=32)
    out_r = fops.flash_attention(q, k, v, use_kernel=False)
    assert out_k.dtype == dt
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=0.05)
