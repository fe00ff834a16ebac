"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.flash_attention as fa_mod
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (flash_attention,
                                          flash_attention_plan)
from repro.kernels.mamba_scan import mamba_scan, mamba_scan_plan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _tol(dtype):
    return TOL[jnp.bfloat16 if jnp.dtype(dtype) == jnp.bfloat16 else jnp.float32]


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,s,t,d", [
    (1, 4, 4, 128, 128, 64),     # MHA, square
    (2, 4, 2, 128, 128, 64),     # GQA group 2
    (1, 8, 2, 256, 256, 64),     # GQA group 4, two q blocks
    (1, 4, 1, 128, 256, 64),     # MQA, cached prefix (t > s)
    (2, 4, 4, 128, 128, 128),    # head_dim 128 (MXU width)
    (1, 12, 1, 256, 256, 128),   # KV group of 12 (starcoder2), two q blocks
    (1, 6, 2, 256, 256, 128),    # KV group of 3 (phi4)
    (1, 12, 1, 300, 300, 64),    # s and t not multiples of the blocks
    (1, 6, 2, 128, 384, 64),     # group of 3 over a cached prefix (t > s)
    (1, 20, 1, 160, 160, 64),    # KV group of 20 (jamba2), not a power of 2
])
def test_flash_attention_matches_ref(b, h, hkv, s, t, d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(keys[0], (b, h, s, d), dtype)
    k = rand(keys[1], (b, hkv, t, d), dtype)
    v = rand(keys[2], (b, hkv, t, d), dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64, 128, 200])
def test_flash_attention_sliding_window(window):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    b, h, s, d = 1, 4, 256, 64
    q = rand(keys[0], (b, h, s, d), jnp.float32)
    k = rand(keys[1], (b, h, s, d), jnp.float32)
    v = rand(keys[2], (b, h, s, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(block_q, block_k):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    b, h, s, d = 1, 2, 256, 64
    q = rand(keys[0], (b, h, s, d), jnp.float32)
    k = rand(keys[1], (b, h, s, d), jnp.float32)
    v = rand(keys[2], (b, h, s, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [0, 100])
def test_flash_attention_kv_chunks(monkeypatch, window):
    """Keys that do not fit VMEM at once stream in chunks along the grid's
    sequential axis; a chunk outside the window is neither computed nor
    fetched anew."""
    import repro.kernels.flash_attention as fa
    monkeypatch.setattr(fa, "KV_VMEM_BYTES", 128 * 2 * 2 * 64 * 4)
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    b, h, hkv, s, t, d = 1, 4, 2, 144, 400, 64
    q = rand(keys[0], (b, h, s, d), jnp.float32)
    k = rand(keys[1], (b, hkv, t, d), jnp.float32)
    v = rand(keys[2], (b, hkv, t, d), jnp.float32)
    plan = fa.flash_attention_plan(s, t, h // hkv, d, jnp.float32,
                                   causal=True, window=window, block_k=128)
    assert plan.n_chunks == 4
    flash_attention.clear_cache()
    out = flash_attention(q, k, v, causal=True, window=window, block_k=128,
                          interpret=True)
    flash_attention.clear_cache()
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(keys[0], (1, 2, 128, 64), jnp.float32)
    k = rand(keys[1], (1, 2, 128, 64), jnp.float32)
    v = rand(keys[2], (1, 2, 128, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _allowed(plan, causal, window):
    """[s_pad, t_pad] bool: the (query, key) pairs the call's mask lets
    through, padded query rows continuing the positions, padded keys none."""
    q = np.arange(plan.s_pad)[:, None] + plan.seq_k - plan.seq_q
    k = np.arange(plan.t_pad)[None, :]
    ok = np.broadcast_to(k < plan.seq_k, (plan.s_pad, plan.t_pad))
    if causal:
        ok = ok & (q >= k)
    if window:
        ok = ok & (q - k < window)
    return ok


@pytest.mark.parametrize("s,t,g,dtype,causal,window", [
    (1024, 1024, 12, jnp.bfloat16, True, 4096),   # starcoder2, served prompt
    (1024, 1024, 3, jnp.bfloat16, True, 0),       # phi4-mini
    (64, 64, 12, jnp.bfloat16, True, 4096),       # chip smoke's prompt
    (300, 700, 3, jnp.float32, True, 200),        # prefix, window in a tile
    (256, 256, 1, jnp.float32, False, 0),         # non-causal
    (100, 40000, 1, jnp.bfloat16, True, 0),       # keys span several chunks
    (1024, 1024, 20, jnp.bfloat16, True, 0),      # jamba2: 20 heads, 1 KV
])
def test_flash_attention_plan_tiles(s, t, g, dtype, causal, window):
    """The plan schedules exactly the tiles the mask needs, masks only the
    ones it cuts, and keeps the blocks whole."""
    plan = flash_attention_plan(s, t, g, 128, dtype, causal=causal,
                                window=window)
    bq, bk = plan.block_q, plan.block_k
    assert bq % 16 == 0 and bk % 16 == 0 and plan.kv_chunk % bk == 0
    ok = _allowed(plan, causal, window)
    tiles = list(plan.tiles())
    assert len(set(tiles)) == len(tiles)
    covered = np.zeros_like(ok)
    for qi, kj, masked in tiles:
        tile = ok[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
        assert tile.any()                     # no dead tile is scheduled
        assert masked == (not tile.all())     # the mask only where it cuts
        covered[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk] = True
    assert not (ok & ~covered)[:s].any()      # every allowed pair is scored


def test_flash_attention_plan_served_shape():
    """At the served shape (starcoder2: q [4, 24, 1024, 128], k/v
    [4, 2, 1024, 128], bf16, causal) no tile above the diagonal is
    scheduled, and the grid has at least 8x fewer steps than the 6,144 of
    one program per (head, q block, kv block) of 128."""
    plan = flash_attention_plan(1024, 1024, 12, 128, jnp.bfloat16,
                                causal=True, window=4096)
    grid = plan.grid(4, 2)
    assert np.prod(grid) * 8 <= 4 * 24 * 8 * 8
    assert plan.group * plan.block_q >= 512      # MXU rows per program
    for qi, kj, _ in plan.tiles():
        assert kj * plan.block_k <= (qi + 1) * plan.block_q - 1


def test_flash_attention_plan_group_of_20():
    """Jamba2's served shape (q [B, 20, 1024, 128] over one KV head, bf16):
    the plan needs no model name. The folded query tile stays within the
    score budget with whole bf16 tiles, and the grid is one program row per
    (batch, KV head) and query block."""
    plan = flash_attention_plan(1024, 1024, 20, 128, jnp.bfloat16,
                                causal=True)
    assert plan.group == 20 and plan.block_q % 16 == 0
    assert plan.group * plan.block_q * plan.block_k <= fa_mod.SCORE_TILE
    assert plan.grid(4, 1) == (4, 1024 // plan.block_q, 1)
    assert plan.s_pad == plan.t_pad == 1024


# --------------------------------------------------------------------------- #
# decode attention (flash-decoding style, ring cache)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,w,d,pos", [
    (1, 4, 4, 128, 64, 64),      # partially-filled cache
    (2, 4, 2, 128, 64, 127),     # cache exactly full
    (1, 8, 2, 256, 64, 300),     # ring wrap-around (pos > W)
    (2, 4, 1, 128, 128, 100),    # MQA, wide head
])
def test_decode_attention_matches_ref(b, h, hkv, w, d, pos, dtype):
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(keys[0], (b, h, d), dtype)
    k = rand(keys[1], (b, hkv, w, d), dtype)
    v = rand(keys[2], (b, hkv, w, d), dtype)
    out = decode_attention(q, k, v, pos, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 96])
def test_decode_attention_window(window):
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    b, h, w, d, pos = 1, 4, 128, 64, 500
    q = rand(keys[0], (b, h, d), jnp.float32)
    k = rand(keys[1], (b, h, w, d), jnp.float32)
    v = rand(keys[2], (b, h, w, d), jnp.float32)
    out = decode_attention(q, k, v, pos, window=window, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_block_sweep():
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    b, h, w, d, pos = 1, 2, 512, 64, 511
    q = rand(keys[0], (b, h, d), jnp.float32)
    k = rand(keys[1], (b, h, w, d), jnp.float32)
    v = rand(keys[2], (b, h, w, d), jnp.float32)
    want = ref.decode_attention_ref(q, k, v, pos)
    for block_k in (128, 256, 512):
        out = decode_attention(q, k, v, pos, block_k=block_k, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"block_k={block_k}")


# --------------------------------------------------------------------------- #
# mamba chunked scan
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,d,n,block_s,block_d", [
    (1, 128, 128, 16, 64, 128),   # two sequence chunks
    (2, 256, 256, 16, 128, 128),  # two channel blocks
    (1, 64, 128, 8, 64, 64),      # narrow state / small blocks
    # the plan's tiling: five 128-lane channel blocks, three 128-step lane
    # tiles in one chunk, 84 padded steps
    (1, 300, 640, 16, None, None),
    (3, 300, 1280, 16, None, None),  # two rows a program, a remainder of one
])
def test_mamba_scan_matches_ref(b, s, d, n, block_s, block_d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x = rand(keys[0], (b, s, d), dtype)
    dt = jax.nn.softplus(rand(keys[1], (b, s, d), jnp.float32)).astype(dtype)
    b_mat = rand(keys[2], (b, s, n), dtype)
    c_mat = rand(keys[3], (b, s, n), dtype)
    a = -jnp.exp(rand(keys[4], (d, n), jnp.float32))  # stable (negative) A
    d_vec = rand(keys[5], (d,), jnp.float32)
    y, h = mamba_scan(x, dt, b_mat, c_mat, a, d_vec,
                      block_d=block_d, block_s=block_s, interpret=True)
    y_ref, h_ref = ref.mamba_scan_ref(x, dt, b_mat, c_mat, a, d_vec)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))
    # the state is f32 from f32 copies of the same inputs, whatever x's dtype
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               **_tol(jnp.float32))


def test_mamba_scan_plan_folds_batch_rows():
    """Narrow state tiles share a program: at d 1280 (no multiple of 384 or
    512) the plan takes 256 channels (4 state vregs), folds two rows, and
    batch 3 leaves one row in the last program."""
    plan = mamba_scan_plan(3, 300, 1280, 16, jnp.float32, jnp.float32)
    assert (plan.block_d, plan.batch_fold) == (256, 2)
    assert plan.grid(3) == (2, 5, 1)
    assert (plan.block_s, plan.s_pad, plan.lane_tile) == (384, 384, 128)


@pytest.mark.parametrize("batch,s,d,io_dtype", [
    (1, 1024, 5120, jnp.float32),      # jamba2_3b as served, each profiled
    (2, 1024, 5120, jnp.float32),      # batch: x bf16, dt/B/C f32
    (4, 1024, 5120, jnp.float32),
    (8, 1024, 5120, jnp.float32),
    (1, 256, 8192, jnp.bfloat16),      # falcon_mamba_7b d_inner
])
def test_mamba_scan_plan_served_shapes(batch, s, d, io_dtype):
    """At the served shapes the state tile is lane-dense (whole 128-lane
    groups dividing D, 8 f32 vregs at N 16), the lane tiles are whole, and
    one program fits v5e's 16 MiB of scoped VMEM."""
    plan = mamba_scan_plan(batch, s, d, 16, jnp.bfloat16, io_dtype)
    assert plan.block_d % 128 == 0 and d % plan.block_d == 0
    assert plan.n_state * plan.block_d == 8 * 8 * 128
    assert plan.block_s % plan.lane_tile == 0 and plan.lane_tile == 128
    assert plan.s_pad == s
    assert plan.vmem_bytes() <= 16 * 2 ** 20
    assert plan.grid(batch)[0] * plan.batch_fold >= batch


def test_mamba_scan_state_carry_chunk_boundary():
    """The carried state across chunk boundaries must equal the sequential
    scan's state — run one long scan vs. the same data with tiny chunks."""
    keys = jax.random.split(jax.random.PRNGKey(8), 6)
    b, s, d, n = 1, 96, 64, 16
    x = rand(keys[0], (b, s, d), jnp.float32)
    dt = jax.nn.softplus(rand(keys[1], (b, s, d), jnp.float32))
    b_mat = rand(keys[2], (b, s, n), jnp.float32)
    c_mat = rand(keys[3], (b, s, n), jnp.float32)
    a = -jnp.exp(rand(keys[4], (d, n), jnp.float32))
    d_vec = rand(keys[5], (d,), jnp.float32)
    y32, h32 = mamba_scan(x, dt, b_mat, c_mat, a, d_vec,
                          block_d=64, block_s=32, interpret=True)
    y96, h96 = mamba_scan(x, dt, b_mat, c_mat, a, d_vec,
                          block_d=64, block_s=96, interpret=True)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y96),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h32), np.asarray(h96),
                               rtol=1e-5, atol=1e-5)
