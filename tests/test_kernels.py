"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (the kernel body executes in
Python on CPU) and must match ref.py to numerical tolerance.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import chase, compute_probe, flash_attention, ref, stream

I = dict(interpret=True)


def _arr(shape, dtype=jnp.float32, seed=0, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return (x * scale).astype(dtype)


# ---------------------------------------------------------------------------
# stream kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,block", [(128, 128), (512, 128), (1024, 512)])
def test_stream_read(rows, block):
    x = _arr((rows, 128))
    out = stream.read_hbm(x, block_rows=block, **I)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.read_ref(x)),
                               rtol=2e-6)


@pytest.mark.parametrize("rows,block", [(256, 128), (512, 512)])
def test_stream_write(rows, block):
    out = stream.write_hbm(rows, value=2.5, block_rows=block, **I)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.write_ref(rows, 2.5)))


@pytest.mark.parametrize("rows", [128, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_rmw(rows, dtype):
    x = _arr((rows, 128), dtype)
    out = stream.rmw_hbm(x, block_rows=128, **I)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.rmw_ref(x), np.float32),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("rows", [128, 1024])
def test_stream_copy(rows):
    x = _arr((rows, 128))
    out = stream.copy_hbm(x, block_rows=128, **I)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_stream_triad():
    b, c = _arr((512, 128), seed=1), _arr((512, 128), seed=2)
    out = stream.triad_hbm(b, c, scalar=3.0, block_rows=128, **I)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.triad_ref(b, c, 3.0)),
                               atol=2e-6)


@pytest.mark.parametrize("rf", [1.0, 2 / 3, 0.5, 1 / 3, 0.0])
def test_stream_mixed(rf):
    """Mixed r/w kernel: read_fraction of the blocks are sum-reduced,
    the rest written — and nothing else touches memory, so the realized
    read:write line ratio is exactly the configured one."""
    rows, block = 1024, 128
    n = rows // block
    x = _arr((rows, 128))
    s, out = stream.mixed_hbm(x, read_fraction=rf, block_rows=block, **I)
    n_r = int(round(n * rf))
    exp_sum = float(np.asarray(x[:n_r * block]).sum())
    np.testing.assert_allclose(float(s), exp_sum, rtol=2e-5)
    assert out.shape == ((n - n_r) * block, 128)   # written lines only
    if n_r < n:
        assert (np.asarray(out) == 1.0).all()


@pytest.mark.parametrize("repeats", [1, 4])
def test_vmem_read_write(repeats):
    x = _arr((256, 128))
    out = stream.read_vmem(x, repeats=repeats, **I)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.read_vmem_ref(x, repeats)),
        rtol=2e-6)
    w = stream.write_vmem(256, repeats=repeats, **I)
    np.testing.assert_array_equal(
        np.asarray(w), np.asarray(ref.write_vmem_ref(256, repeats)))


# ---------------------------------------------------------------------------
# pointer chase
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_lines", [2, 16, 64, 257])
@pytest.mark.parametrize("seed", [0, 3])
def test_chase_vmem_matches_ref(n_lines, seed):
    buf = jnp.asarray(chase.chain_buffer(n_lines, seed))
    for steps in (1, n_lines // 2 or 1, n_lines):
        out = chase.chase_vmem(buf, n_steps=steps, **I)
        assert int(out) == ref.chase_ref(np.asarray(buf), steps)


@pytest.mark.parametrize("n_lines", [8, 64])
def test_chase_hbm_matches_ref(n_lines):
    buf = jnp.asarray(chase.chain_buffer(n_lines, 1))
    out = chase.chase_hbm(buf, n_steps=n_lines, **I)
    assert int(out) == ref.chase_ref(np.asarray(buf), n_lines) == 0


def test_chain_is_single_cycle():
    for n in (1, 2, 7, 64, 100):
        nxt = chase.make_chain(n, seed=2)
        seen, idx = set(), 0
        for _ in range(n):
            assert idx not in seen
            seen.add(idx)
            idx = int(nxt[idx])
        assert idx == 0 and len(seen) == n


@pytest.mark.parametrize("n", [1, 2, 3, 4, 17, 512, 4097, 131072])
@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1])
def test_chain_is_the_reference_sattolo_bit_for_bit(n, seed):
    """The bulk-drawn chain is exactly the permutation the plain Sattolo
    loop draws from the same seed: the benchmark checks every chase's
    final index against that loop."""
    from bench.reference import probes
    got = chase.make_chain(n, seed)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, probes.sattolo(n, seed))


@pytest.mark.parametrize("shape", [(1,), (64,), (3, 257)])
def test_lay_lines_matches_the_host_layout(shape):
    succ = np.arange(np.prod(shape), dtype=np.int32).reshape(shape)[..., ::-1]
    buf = np.zeros(shape + (chase.LANE,), np.int32)
    buf[..., 0] = succ
    out = chase.lay_lines(succ)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), buf)


@pytest.mark.parametrize("stride", [1, 4, 8, 50])
def test_strided_chain_is_single_cycle(stride):
    for n in (1, 2, 7, 64, 100):
        nxt = chase.make_strided_chain(n, stride)
        seen, idx = set(), 0
        for _ in range(n):
            assert idx not in seen
            seen.add(idx)
            idx = int(nxt[idx])
        assert idx == 0 and len(seen) == n


def test_strided_chain_constant_hop():
    nxt = chase.make_strided_chain(64, 8)
    hops = {(int(nxt[i]) - i) % 64 for i in range(64)}
    assert len(hops) == 1            # every hop covers the same distance
    buf = jnp.asarray(chase.strided_chain_buffer(64, 8))
    out = chase.chase_vmem(buf, n_steps=64, **I)
    assert int(out) == 0             # full cycle returns home


# ---------------------------------------------------------------------------
# compute probe
# ---------------------------------------------------------------------------


def test_mxu_probe():
    a = jnp.eye(128, dtype=jnp.float32) * 0.5
    out = compute_probe.mxu_probe(a, iters=3, **I)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.mxu_probe_ref(a, 3)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention: sweep (B, H, KVH, S, D) x causal x window x dtype
# ---------------------------------------------------------------------------

CASES = [
    # b, h, kvh, sq, d, causal, window
    (1, 1, 1, 128, 64, True, 0),
    (2, 4, 2, 256, 64, True, 0),       # GQA
    (1, 4, 1, 256, 128, True, 0),      # MQA
    (1, 2, 2, 256, 64, False, 0),      # bidirectional
    (1, 4, 2, 512, 64, True, 128),     # sliding window
    (2, 2, 1, 256, 32, True, 64),      # window + GQA + small head
]


@pytest.mark.parametrize("b,h,kvh,s,d,causal,window", CASES)
def test_flash_attention_vs_ref(b, h, kvh, s, d, causal, window):
    q = _arr((b, h, s, d), seed=1, scale=0.5)
    k = _arr((b, kvh, s, d), seed=2, scale=0.5)
    v = _arr((b, kvh, s, d), seed=3, scale=0.5)
    out = flash_attention.flash_attention(
        q, k, v, causal=causal, window=window, block_q=128, block_k=128,
        **I)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, 2e-2)])
def test_flash_attention_bf16(dtype, atol):
    q = _arr((1, 2, 256, 64), dtype, seed=1, scale=0.5)
    k = _arr((1, 1, 256, 64), dtype, seed=2, scale=0.5)
    v = _arr((1, 1, 256, 64), dtype, seed=3, scale=0.5)
    out = flash_attention.flash_attention(q, k, v, causal=True, **I)
    expect = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=atol)


def test_flash_attention_block_shape_independence():
    """Result must not depend on the BlockSpec tiling — including when
    the sequence does NOT divide the block shape (padded kv tail)."""
    q = _arr((1, 2, 512, 64), seed=4, scale=0.3)
    k = _arr((1, 2, 512, 64), seed=5, scale=0.3)
    v = _arr((1, 2, 512, 64), seed=6, scale=0.3)
    outs = [
        np.asarray(flash_attention.flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, **I))
        for bq, bk in ((128, 128), (256, 128), (128, 256), (512, 512),
                       (96, 160), (200, 200))]     # seq % block != 0
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


@pytest.mark.parametrize("s,causal,window", [(192, True, 0), (320, True, 64),
                                             (160, False, 0)])
def test_flash_attention_ragged_seq_vs_ref(s, causal, window):
    """seq % 128 != 0: padding + masking must still match the oracle."""
    q = _arr((1, 2, s, 64), seed=1, scale=0.5)
    k = _arr((1, 2, s, 64), seed=2, scale=0.5)
    v = _arr((1, 2, s, 64), seed=3, scale=0.5)
    out = flash_attention.flash_attention(
        q, k, v, causal=causal, window=window, block_q=128, block_k=128,
        **I)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


@pytest.mark.parametrize("n_lines", [8, 64])
def test_chase_hbm_walks_a_stacked_buffer_chain_by_chain(n_lines):
    """A stacked (G, n, 128) buffer walks its G chains one after another
    inside one kernel and returns each chain's own final index."""
    bufs = np.stack([chase.chain_buffer(n_lines, s) for s in (0, 1, 2)])
    out = chase.chase_hbm(jnp.asarray(bufs), n_steps=n_lines - 1, **I)
    assert [int(v) for v in out] == [
        ref.chase_ref(b, n_lines - 1) for b in bufs]


def test_interpret_mode_is_refused_in_a_tpu_process(monkeypatch):
    """A TPU process never runs a kernel in interpret mode: the default
    resolves to compiled, and an explicit request raises."""
    from repro.kernels import ops
    assert ops._interp(None) is (not ops.on_tpu())
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops._interp(None) is False
    assert ops._interp(False) is False
    with pytest.raises(ValueError, match="interpret"):
        ops._interp(True)
